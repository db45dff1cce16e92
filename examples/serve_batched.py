"""Batched serving example: prefill a prompt batch, then greedy-decode.

Exercises the production serve path (prefill -> KV/state cache -> decode
steps) for a dense, an SSM, and an MoE architecture. Prompt batches come
from the `repro.data` plane (`lm_markov` source behind a ShardedLoader), so
the serve path consumes the same loader abstraction the trainers do.

    PYTHONPATH=src python examples/serve_batched.py
    PYTHONPATH=src python examples/serve_batched.py --no-smoke \
        --archs yi-6b --decode-steps 4     # full config (slow on CPU)
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.base import ParallelConfig, TrainConfig
from repro.data import ShardedLoader, get_source
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.train import serve, trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family configs (--no-smoke = full)")
    ap.add_argument("--archs", nargs="+",
                    default=["yi-6b", "xlstm-125m", "phi3.5-moe-42b-a6.6b"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=12)
    args = ap.parse_args()

    mesh = make_host_mesh(1, 1)

    for arch in args.archs:
        cfg = registry.smoke_config(arch) if args.smoke else \
            registry.get_spec(arch).cfg
        spec = registry.get_spec(arch)
        # prompts through the data plane: one loader batch per arch
        prompts = ShardedLoader(
            get_source("lm_markov", vocab_size=cfg.vocab_size,
                       seq_len=args.prompt_len, batch_size=args.batch,
                       encdec_d_model=(cfg.d_model if cfg.family == "encdec"
                                       else 0)),
            mesh, placement="device", prefetch=0)
        with jax.set_mesh(mesh):
            state = trainer.init_state(spec, cfg,
                                       TrainConfig(optimizer="sgd"),
                                       ParallelConfig(), jax.random.PRNGKey(1))
            lm_batch = next(iter(prompts.batches(1)))
            batch = {"tokens": lm_batch["tokens"]}
            if cfg.family == "encdec":
                batch["frames"] = lm_batch["frames"]
            t0 = time.time()
            toks = serve.greedy_decode(spec, cfg, state["params"], batch,
                                       args.decode_steps,
                                       ParallelConfig(seq_shard=False))
            dt = time.time() - t0
        print(f"{arch:24s} decoded {toks.shape[0]}x{toks.shape[1]} tokens "
              f"in {dt:5.2f}s -> {np.asarray(toks[0, :8])}")
    print("OK")


if __name__ == "__main__":
    main()
