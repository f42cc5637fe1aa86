"""ResNet-50 gradient push/pull trace (BASELINE config 4).

BytePS's flagship workload is the ResNet-50 gradient stream: ~25.5M fp32
params (~102 MB) pushed and pulled every step.  The reference has no model
code; the trace is the traffic shape.  We synthesize the exact per-tensor
sizes from the architecture ([3,4,6,3] bottleneck blocks) and replay them
through the collective engine as bucketed dense push_pulls.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def resnet50_param_sizes() -> List[Tuple[str, int]]:
    """(name, float32 element count) per tensor, ~25.5M total."""
    sizes: List[Tuple[str, int]] = []

    def conv(name, kh, kw, cin, cout):
        sizes.append((f"{name}.weight", kh * kw * cin * cout))
        sizes.append((f"{name}.bn", 2 * cout))  # gamma+beta

    conv("stem", 7, 7, 3, 64)
    cin = 64
    widths = [(64, 256), (128, 512), (256, 1024), (512, 2048)]
    blocks = [3, 4, 6, 3]
    for stage, ((mid, out), n) in enumerate(zip(widths, blocks)):
        for b in range(n):
            base = f"layer{stage + 1}.{b}"
            conv(f"{base}.conv1", 1, 1, cin, mid)
            conv(f"{base}.conv2", 3, 3, mid, mid)
            conv(f"{base}.conv3", 1, 1, mid, out)
            if b == 0:
                conv(f"{base}.downsample", 1, 1, cin, out)
            cin = out
    sizes.append(("fc.weight", 2048 * 1000))
    sizes.append(("fc.bias", 1000))
    return sizes


def total_params() -> int:
    return sum(n for _, n in resnet50_param_sizes())


def make_buckets(bucket_bytes: int = 4 << 20) -> List[Tuple[str, int]]:
    """Size-bucketing of the gradient stream: small tensors fuse into
    ~partition-sized buckets and oversized tensors split into
    partition-sized chunks (the reference's BYTEPS_PARTITION_BYTES
    semantics, rdma_transport.h:591-617)."""
    buckets: List[Tuple[str, int]] = []
    cur = 0
    idx = 0
    limit = bucket_bytes // 4  # fp32 elements

    def flush():
        nonlocal cur, idx
        if cur:
            buckets.append((f"rn50_bucket{idx}", cur))
            idx += 1
            cur = 0

    for _, n in resnet50_param_sizes():
        while n >= limit:
            flush()
            buckets.append((f"rn50_bucket{idx}", limit))
            idx += 1
            n -= limit
        if cur + n > limit:
            flush()
        cur += n
    flush()
    return buckets


def replay(engine, steps: int = 1, bucket_bytes: int = 4 << 20,
           grouped: bool = True, host_origin: bool = False,
           overlap: bool = True):
    """Run the ResNet-50 push/pull trace through a CollectiveEngine.

    ``grouped=True`` pushes the whole gradient stream as ONE jitted
    program per step (engine.push_pull_group) — one dispatch instead of
    ~35; ``False`` replays bucket-by-bucket (the per-message analog).

    ``host_origin=True`` replays the path real users hit: each bucket's
    gradient starts as a host numpy array every step (the framework
    hands the PS CPU tensors).  With ``overlap=True`` the next bucket's
    host->HBM staging runs on a background thread while the current
    bucket's collective executes — the pinned-memory/async-RDMA overlap
    of the reference's host path; ``overlap=False`` stages serially
    (the baseline the overlap is measured against).

    Returns (bytes_moved_per_step, host seconds_per_step).
    """
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    buckets = make_buckets(bucket_bytes)
    grads = {}
    host = {}
    sharding = NamedSharding(engine.mesh, P(engine.axis, None))
    for name, n in buckets:
        engine.register_dense(name, np.arange(1, dtype=np.uint64), n)
        bucket = engine.bucket(name)
        if host_origin:
            host[name] = np.ones(
                (engine.num_shards, bucket.padded_len), np.float32
            )
        else:
            g = jnp.ones(
                (engine.num_shards, bucket.padded_len), jnp.float32
            )
            grads[name] = jax.device_put(g, sharding)
    names = [name for name, _ in buckets]
    # Grouped dispatch supports stateless handles only; engines built
    # with fused optimizer handles fall back to per-bucket replay.
    grouped = grouped and not engine.handle_is_stateful and not host_origin

    def one_step():
        if grouped:
            engine.push_pull_group(names, [grads[n] for n in names])
        elif not host_origin:
            for n in names:
                engine.push_pull(n, grads[n])
        elif not overlap:
            for n in names:
                engine.push_pull(n, host[n])
        else:
            # Double-buffered host staging via the engine's hardened
            # stream pipeline: bucket i+1's transfer runs on the stager
            # thread while bucket i's collective dispatches.
            for _ in engine.push_pull_multi_stream(
                ((n, host[n]) for n in names), depth=2
            ):
                pass

    # Warm the executable cache (the rendezvous-equivalent first touch).
    one_step()
    engine.block()

    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    engine.block()
    dt = (time.perf_counter() - t0) / max(steps, 1)
    step_bytes = 2 * 4 * sum(n for _, n in buckets)  # push + pull
    return step_bytes, dt
