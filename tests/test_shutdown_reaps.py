"""`shutdown()` returns only when the workers it had to kill are gone.

A worker that held accelerators gives them back when the kernel has torn
its mappings down, seconds after its `os._exit` at 16 GB a chip; shutdown
waited two seconds, killed, and returned, and a process that opened the
device straight after failed with "Device or resource busy" (a benchmark
cell after another's, PR 34).  Here a stopped worker stands for the slow
one: killed after the two seconds, it was left a zombie before."""

import os
import signal
import time

import ray_tpu


def test_a_killed_worker_is_reaped_before_shutdown_returns():
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def pid():
        return os.getpid()

    worker = ray_tpu.get(pid.remote())
    time.sleep(0.2)         # its lease is given back: it idles in the pool
    os.kill(worker, signal.SIGSTOP)     # it will not act on "exit"
    ray_tpu.shutdown()
    try:
        os.kill(worker, 0)
    except ProcessLookupError:
        return
    os.kill(worker, signal.SIGKILL)
    raise AssertionError("shutdown returned with a worker not yet reaped")
