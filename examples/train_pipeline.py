"""Train the flagship transformer with PIPELINE parallelism (dp x pp).

The layer stack is sharded across pipeline stages (each stage owns its
key range of layers — the PS sharding applied to depth), microbatches
stream through a GPipe schedule, and an optional leading data-parallel
axis averages gradients across replicas.  On a CPU dev box::

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_pipeline.py --steps 20
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--micro", type=int, default=2, help="microbatches")
    ap.add_argument("--mb", type=int, default=2, help="microbatch size")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.2)
    args = ap.parse_args()

    import jax
    import numpy as np

    from pslite_tpu.models.train import make_pp_train_step
    from pslite_tpu.models.transformer import ModelConfig
    from pslite_tpu.parallel.mesh import make_mesh
    from pslite_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    n = len(jax.devices())
    pp = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    if pp == 1:
        raise SystemExit("need an even device count for a pipeline")
    dp = n // pp
    mesh = (
        make_mesh((dp, pp), ("dp", "pp")) if dp > 1
        else make_mesh((pp,), ("pp",))
    )
    print(f"devices={n} mesh=(dp={dp}, pp={pp}) "
          f"platform={jax.devices()[0].platform}")

    cfg = ModelConfig(vocab=256, dim=args.dim, heads=4, layers=pp)
    step, state, tok_sharding = make_pp_train_step(
        cfg, mesh, lr=args.lr, num_micro=args.micro
    )

    rng = np.random.default_rng(0)
    shape = (
        (dp, args.micro, args.mb, args.seq) if dp > 1
        else (args.micro, args.mb, args.seq)
    )
    inputs = rng.integers(0, cfg.vocab, size=shape).astype(np.int32)
    targets = (inputs + 1) % cfg.vocab
    inputs = jax.device_put(inputs, tok_sharding)
    targets = jax.device_put(targets, tok_sharding)

    t0 = time.perf_counter()
    for i in range(args.steps):
        state, loss = step(state, inputs, targets)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    dt = (time.perf_counter() - t0) / args.steps
    print(f"{dt * 1e3:.1f} ms/step "
          f"(bubble {(pp - 1)}/{args.micro + pp - 1} of ticks)")


if __name__ == "__main__":
    main()
