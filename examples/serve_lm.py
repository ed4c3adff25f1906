"""Serve a continuous-batched LM behind HTTP with streaming tokens.

Run: python examples/serve_lm.py        (fails without a chip)
Then: curl -N 'http://127.0.0.1:8000/lm?stream=1' -d '{"prompt": [1,2,3]}'

The replica asks for one TPU chip, so its worker leases the chip and
runs with JAX_PLATFORMS=tpu; this driver never touches jax.
"""
import ray_tpu
from ray_tpu import serve


@serve.deployment(name="lm", ray_actor_options={"num_tpus": 1})
class LM:
    def __init__(self):
        import dataclasses

        from ray_tpu.models.transformer import PRESETS
        from ray_tpu.serve.llm import LLMDeployment
        self.llm = LLMDeployment(
            cfg_kwargs=dataclasses.asdict(PRESETS["gpt2-small"]),
            num_slots=8, max_len=256)

    async def __call__(self, body):
        out = await self.llm.generate(body["prompt"],
                                      max_new=body.get("max_new", 16))
        return {"tokens": out["tokens"], "ttft_s": out["ttft_s"]}

    def stream(self, body):
        yield from self.llm.generate_stream(
            body["prompt"], max_new=body.get("max_new", 16))


def main():
    ray_tpu.init()
    if not ray_tpu.cluster_resources().get("TPU"):
        raise SystemExit("no TPU chip found: this example serves from "
                         "a worker that leases one")
    serve.run(LM.bind(), name="lm", route_prefix="/lm")
    httpd = serve.start_http_proxy(port=8000)
    print(f"serving on http://127.0.0.1:{httpd.server_address[1]}/lm")
    import threading
    threading.Event().wait()


if __name__ == "__main__":
    main()
