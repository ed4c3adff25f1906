"""Traffic kind `train_job`: sequences of `seq_len` tokens, a fresh batch
every step, for at most `max_steps` steps (the window ends the job first).
Nothing is offered from the driver: the job's own `ray_tpu.data` feed makes
the rows in the worker, so the kind only names its cell runner."""

CELL = "train_cell"      # benchmarks/lib/train_cell.py runs the cell
