"""Train the flagship transformer through the PS data plane.

Single process drives the whole device mesh (every device is worker AND
server shard — the JOINT deployment).  On a TPU slice this runs over ICI;
on a CPU dev box, force a virtual mesh::

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_flagship.py --steps 20

Add ``--moe`` for the expert-parallel variant.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--moe", action="store_true")
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args()

    import jax

    from pslite_tpu.checkpoint import save_train_state
    from pslite_tpu.models.train import make_ps_train_step, toy_batch
    from pslite_tpu.models.transformer import ModelConfig
    from pslite_tpu.parallel.mesh import make_mesh
    from pslite_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    n = len(jax.devices())
    sp = 2 if n % 2 == 0 else 1
    mesh = make_mesh((n // sp, sp), ("dp", "sp"))
    print(f"devices={n} mesh=(dp={n // sp}, sp={sp}) "
          f"platform={jax.devices()[0].platform} "
          f"device_kind={jax.devices()[0].device_kind}")

    cfg = ModelConfig(
        vocab=256, dim=args.dim, heads=4, layers=args.layers,
        moe_experts=4 * sp if args.moe else 0,
    )
    step, store, tok_sharding, _ = make_ps_train_step(cfg, mesh, lr=args.lr)

    # Batch shards over dp and sequence over sp: round both up so the
    # example runs on any slice size.
    dp = n // sp
    batch = -(-args.batch // dp) * dp
    seq = -(-args.seq // sp) * sp
    if (batch, seq) != (args.batch, args.seq):
        print(f"note: batch/seq padded to mesh factors: "
              f"batch {args.batch}->{batch}, seq {args.seq}->{seq}")
    inputs, targets = toy_batch(cfg, batch=batch, seq=seq)
    inputs = jax.device_put(inputs, tok_sharding)
    targets = jax.device_put(targets, tok_sharding)

    # Warm up (jit compile) before timing, like pslite_tpu/benchmark.py.
    store, loss = step(store, inputs, targets)
    print(f"step {0:4d}  loss {float(loss):.4f}  (compile)")
    timed_steps = args.steps - 1
    t0 = time.perf_counter()
    for i in range(1, args.steps):
        store, loss = step(store, inputs, targets)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    store.block_until_ready()
    dt = time.perf_counter() - t0
    if timed_steps > 0:
        toks = batch * seq * timed_steps
        print(f"{toks / dt:,.0f} tokens/s (steady state, "
              f"{timed_steps} timed steps)")
    else:
        print("(need --steps >= 2 for a steady-state throughput number)")

    if args.checkpoint:
        written = save_train_state(store, args.steps, args.checkpoint)
        print(f"saved {written}")


if __name__ == "__main__":
    main()
