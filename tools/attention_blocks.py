"""Device time of one layer's causal attention, forward and forward with
backward, at GPT-2 medium's training shape (4 rows x 16 heads x 1,024
positions x 64), computed two ways: the splash kernel at square blocks
of 128 to 1,024, built by the program's own
``models/dnn/transformer._splash_kernel`` (whose default block at 1,024
is 512), and ``parallel.ring_attention.ring_attention`` on a one-shard
``seq`` axis (the whole f32 square, the path GPT-2's block ran before
the kernel).

    python3 tools/attention_blocks.py [--batch 4] [--seq 1024] \\
        [--heads 16] [--d-head 64] [--reps 20]

Inputs are bf16 from a fixed key. Prints one JSON line: milliseconds a
call, the least of three timed loops of ``--reps`` calls each, for each
form and pass. On a CPU it runs the kernels in the Pallas interpreter:
the numbers then say nothing of the chip.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _ms(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))                  # compile, warm
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / reps)
    return best * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--d-head", type=int, default=64)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.models.dnn.transformer import _splash_kernel
    from mmlspark_tpu.parallel.compat import shard_map
    from mmlspark_tpu.parallel.mesh import make_mesh
    from mmlspark_tpu.parallel.ring_attention import ring_attention

    B, S, H, D = args.batch, args.seq, args.heads, args.d_head
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
                  for kk in keys)
    scale = D ** -0.5

    def splash_at(b):
        kernel = jax.vmap(_splash_kernel(S, H, 0, True, block=b))
        return lambda q, k, v: kernel(q * scale, k, v)

    mesh = make_mesh({"seq": 1}, devices=jax.devices()[:1])
    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh, in_specs=(P(None, None, "seq"),) * 3,
        out_specs=P(None, None, "seq"), check_vma=False)

    forms = {f"splash_{b}": splash_at(b)
             for b in (128, 256, 512, 1024) if b <= S and S % b == 0}
    forms["ring_one_shard"] = ring
    out = {"device": jax.devices()[0].device_kind,
           "shape": [B, H, S, D], "reps": args.reps}
    for name, attn in forms.items():
        fwd = jax.jit(attn)
        both = jax.jit(jax.grad(
            lambda q, k, v, attn=attn: jnp.vdot(
                attn(q, k, v).astype(jnp.float32), g.astype(jnp.float32)),
            argnums=(0, 1, 2)))
        out[name] = {"fwd_ms": _ms(fwd, (q, k, v), args.reps),
                     "fwd_bwd_ms": _ms(both, (q, k, v), args.reps)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
