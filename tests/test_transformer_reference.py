"""``make_train_step`` of GPT-2's dense block against the plain float32
reference (``_moe_reference.dense_logits``), on one device and on a
``{data: 2, seq: 2, model: 2}`` mesh: the comparison of
``test_moe_block.py``, with its tolerances for the dense block and their
readings written there. The 2 x 2 x 2 mesh reads the gradient twice over
without the step's ``1 / tp`` (every tensor at a gap of 1.0), which AdamW's
update, the losses and the comparison of meshes with one another do not
see."""

import pytest

from test_moe_block import TOL, check, check_control

CASES = {"dense-1x1x1": ("dense", {"data": 1, "seq": 1, "model": 1}),
         "dense-2x2x2": ("dense", {"data": 2, "seq": 2, "model": 2})}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("what", sorted(TOL["dense"]))
def test_step_matches_float32_reference(case, what):
    check(case, what, CASES)


def test_float8_control_fails_the_logits_and_the_gradient():
    check_control("dense")


# GPT-2's block on one `seq` shard computes attention with the splash
# kernel where the local sequence is a multiple of 128, and with
# `seq_attention`'s schedule elsewhere: (mesh, S, causal, kernel counted).
# At 768 the kernel's block is 256, the largest that divides S; with two
# `model` shards each runs the kernel over its own half of the heads
ATTENTION_PATHS = {
    "splash-causal": ({"data": 1, "seq": 1, "model": 1}, 128, True,
                      "splash"),
    "splash-full": ({"data": 1, "seq": 1, "model": 1}, 128, False,
                    "splash"),
    "splash-s768": ({"data": 1, "seq": 1, "model": 1}, 768, True,
                    "splash"),
    "splash-tp2": ({"data": 1, "seq": 1, "model": 2}, 128, True, "splash"),
    "ring-seq2": ({"data": 1, "seq": 2, "model": 1}, 128, True, "ring"),
    "ring-s64": ({"data": 1, "seq": 1, "model": 1}, 64, True, "ring"),
}
KERNELS = ("splash", "ring", "ring_zigzag", "ulysses")


@pytest.mark.parametrize("case", sorted(ATTENTION_PATHS))
def test_attention_path_by_seq_shards_and_length(case):
    """The build counts the path it took in
    ``lm_attention_kernel_total{kernel}``, and attention through that path
    matches ``local_attention`` in f32 within bf16 tolerances, in the
    output and in the q, k and v gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.dnn import transformer as tf
    from mmlspark_tpu.observability import metrics
    from mmlspark_tpu.parallel import mesh as meshlib
    from mmlspark_tpu.parallel.compat import shard_map
    from mmlspark_tpu.parallel.placement import pspec as P
    from mmlspark_tpu.parallel.ring_attention import local_attention

    shape, S, causal, want = ATTENTION_PATHS[case]
    n = int(np.prod(list(shape.values())))
    mesh = meshlib.make_mesh(shape, devices=jax.devices()[:n])
    cfg = tf.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                               d_head=8, n_layers=1, d_ff=64, max_len=S,
                               remat=True)

    def counts():
        return {k: metrics.counter("lm_attention_kernel_total",
                                   kernel=k).value for k in KERNELS}

    before = counts()
    params = jax.eval_shape(lambda: tf.init_params(
        cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, S), jnp.int32)
    tf.make_forward(cfg, mesh, causal=causal).lower(params, tokens)
    delta = {k: v - before[k] for k, v in counts().items()}
    assert delta == {**dict.fromkeys(KERNELS, 0), want: 1}, delta

    B, H, D = 2, cfg.n_heads, cfg.d_head
    keys = jax.random.split(jax.random.PRNGKey(42), 4)
    q, k, v, g = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
                  for kk in keys)

    def program(q, k, v):
        if want == "splash":             # the block hands it q scaled
            q = (q.astype(jnp.float32) * D ** -0.5).astype(q.dtype)
        return tf._attend(q, k, v, want, causal)

    spec = P(None, "model", "seq")
    local = shard_map(program, mesh=mesh, in_specs=(spec,) * 3,
                      out_specs=spec, check_vma=False)
    out, vjp = jax.vjp(local, q, k, v)
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: local_attention(q, k, v, causal=causal),
        *(a.astype(jnp.float32) for a in (q, k, v)))
    got = (out,) + vjp(g)
    refs = (ref,) + ref_vjp(g.astype(jnp.float32))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, refs):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        gap = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert gap < 1e-2, (case, name, gap)
