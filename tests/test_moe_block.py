"""``make_train_step`` against a plain float32 reference (``_moe_reference``)
on the CPU at a toy size, for the routed-expert block: RMSNorm, a router read
before attention, GQA, global NoPE and windowed RoPE layers, ReGLU experts
held in part, the splash and megablox kernels in the Pallas interpreter.
``test_transformer_reference.py`` runs the same comparison over GPT-2's
dense block, and ``test_moe_layer.py`` holds one layer of the program alone.

The program computes with bf16 operands and f32 sums (its router in f32),
the reference in f32. Each number compared is relative to the reference's
own size: the first forward's logits, the first step's loss, the worst
weight tensor's gradient as AdamW's first moment holds it
(``|g_prog - g_ref| / |g_ref|``), the worst tensor's norm of the change of
the weights over three steps against the reference's, and the losses of
steps 2 and 3. ``TOL`` gives each block's tolerances, set from the readings
of this file's seeds (sound / float8 control):

- routed experts: logits 0.028 / 0.159, loss 1.1e-4 / 4.0e-4, later losses
  5.5e-4 / 1.3e-3, gradient 0.128 / 0.422, change 0.032 / 0.043. Wider than
  the dense block's because a token whose top-2 choice of expert flips on
  bf16 rounding (a few of 256 a layer: ``TALLY``) takes another expert's
  output and gradient, and that moves every tensor upstream of it; in f32
  the same step reads 3e-7 (logits) and 9e-7 (gradient);
- dense: logits 0.0056 / 0.065, loss 2e-5 / 5.4e-4, later 3e-5 / 1.3e-3,
  gradient 0.014 / 0.145, change 0.0078 / 0.058. Since the one-shard case
  (``dense-1x1x1``, 128 positions) runs the splash kernel, it reads logits
  0.0058, loss 5.5e-5, later 6.0e-5, gradient 0.011, change 0.011; the
  whole f32 square, ``ring_attention`` on that shard, read 0.0058, 6.2e-5,
  2.8e-5, 0.0099 and 0.012 on the same machine. ``dense-2x2x2`` (two
  ``seq`` shards, the ring) reads 0.0058, 6.2e-5, 7.5e-5, 0.010, 0.010.

Each tolerance sits about twice over the sound reading, and the float8
control (e4m3 forward, e5m2 gradients) in the program's place fails the
logits' and the gradient's on both blocks: a program that computed below
its stated precision would too.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _moe_reference as ref
from mmlspark_tpu.models.dnn import transformer as tf
from mmlspark_tpu.parallel import mesh as meshlib

TOL = {"moe": {"logits": 0.07, "loss": 5e-4, "later": 3e-3, "grad": 0.25,
               "change": 0.08},
       "dense": {"logits": 0.02, "loss": 2e-4, "later": 3e-4, "grad": 0.05,
                 "change": 0.03}}
# the first step's assignments to the held experts, sum |n_prog - n_ref| /
# sum n_ref: 0.0049 read, a flip moving one assignment of 512 a layer
TALLY = 0.02
SEQ, STEPS = 128, 3
MOE_SIZES = dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                 d_head=16, n_layers=4, d_expert=32, n_experts=8,
                 held_first=0, held_count=4, top_k=2,
                 windows=(0, 32, 32, 32), rope=(0, 1, 1, 1),
                 rope_theta=1.5e6, eps=1e-6)
DENSE_SIZES = dict(vocab_size=256, d_model=32, n_heads=4, d_head=8)


def moe_cfg(held=(0, 4), dtype=jnp.bfloat16):
    s = MOE_SIZES
    return tf.TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["d_model"],
        n_heads=s["n_heads"], d_head=s["d_head"], n_layers=s["n_layers"],
        d_ff=s["d_expert"], max_len=SEQ, dtype=dtype, remat=True,
        n_experts=s["n_experts"], top_k=s["top_k"], experts_held=held,
        n_kv_heads=s["n_kv_heads"], norm_eps=s["eps"],
        rope_theta=s["rope_theta"], rope_layers=s["rope"],
        windows=s["windows"], loss_chunk=64)


DENSE_CFG = tf.TransformerConfig(vocab_size=256, d_model=32, n_heads=4,
                                 d_head=8, n_layers=2, d_ff=128,
                                 max_len=SEQ, remat=True)


def _jittered(params, key):
    """The program's initial weights with every norm scale and bias moved
    off its start, so that their gradients are held too."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, leaf) in zip(keys, leaves):
        name = jax.tree_util.keystr(path)
        if any(t in name for t in ("norm", "ln", "bias", "'b1'", "'b2'")):
            leaf = leaf + 0.02 * jax.random.normal(k, leaf.shape)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init(cfg, seed):
    return _jittered(tf.init_params(cfg, jax.random.PRNGKey(seed)),
                     jax.random.PRNGKey(seed + 1))


def _batches(vocab, rows):
    rng = np.random.default_rng(41)
    ids = rng.integers(0, vocab, (STEPS, rows, SEQ + 1)).astype(np.int32)
    return [(jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:])) for b in ids]


def _program(cfg, mesh_shape, params, batches):
    n = int(np.prod(list(mesh_shape.values())))
    mesh = meshlib.make_mesh(mesh_shape, devices=jax.devices()[:n])
    copy = jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), params)
    p = tf.shard_params(copy, cfg, mesh)
    opt = tf.shard_opt_state(tf.adamw_init(p, cfg), cfg, mesh)
    out = {"logits": np.asarray(tf.make_forward(cfg, mesh)(p, batches[0][0])),
           "losses": []}
    step = tf.make_train_step(cfg, mesh)
    for i, (tokens, targets) in enumerate(batches):
        p, opt, value = step(p, opt, tokens, targets)
        out["losses"].append(float(value))
        if i == 0:
            out["grad"] = jax.tree_util.tree_map(
                lambda m: np.asarray(m) / 0.1, opt["mu"])
            if cfg.n_experts:
                out["tally"] = tf.expert_tally(opt)
    out["params"] = jax.device_get(p)
    return out


def _reference(params, batches, block, quant=False):
    s, logits_fn = ((MOE_SIZES, ref.moe_logits) if block == "moe"
                    else (DENSE_SIZES, ref.dense_logits))
    out = ref.adamw(params, batches, s, logits_fn, quant=quant)
    out["logits"] = np.asarray(jax.jit(functools.partial(
        ref.logits, s=s, logits_fn=logits_fn, quant=quant))(
            params, batches[0][0])[0])
    out["counts"] = np.asarray(out["counts"])
    return out


def _worst(a, b, rel):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(rel, a, b)))


def gaps(prog, want, params0):
    """The numbers that the tolerances hold, ``prog`` against ``want``."""
    def diff(x, y):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        return np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-30)

    def change(x, y, x0):
        x, y, x0 = (np.asarray(v, np.float64) for v in (x, y, x0))
        moved = np.linalg.norm(y - x0)
        return abs(np.linalg.norm(x - x0) - moved) / max(moved, 1e-30)

    out = {"logits": diff(prog["logits"], want["logits"]),
           "loss": abs(prog["losses"][0] - want["losses"][0])
           / abs(want["losses"][0]),
           "later": max(abs(a - b) / abs(b) for a, b in zip(
               prog["losses"][1:], want["losses"][1:])),
           "grad": _worst(prog["grad"], want["grad"], diff),
           "change": max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
               change, prog["params"], want["params"], params0)))}
    return out


CASES = {"moe-1x1x1": ("moe", {"data": 1, "seq": 1, "model": 1})}


@functools.lru_cache(maxsize=None)
def _setup(block):
    cfg = moe_cfg() if block == "moe" else DENSE_CFG
    params = jax.device_get(_init(cfg, 41))
    batches = _batches(cfg.vocab_size, 2)
    return cfg, params, batches, _reference(params, batches, block)


@functools.lru_cache(maxsize=None)
def run_case(block, mesh_shape):
    cfg, params, batches, want = _setup(block)
    prog = _program(cfg, dict(mesh_shape), params, batches)
    return prog, want, gaps(prog, want, params)


def check(case, what, cases):
    block, mesh_shape = cases[case]
    got = run_case(block, tuple(mesh_shape.items()))[2][what]
    assert got < TOL[block][what], (case, what, got)


def check_control(block):
    cfg, params, batches, want = _setup(block)
    control = gaps(_reference(params, batches, block, quant=True), want,
                   params)
    for what in ("logits", "grad"):
        assert control[what] > TOL[block][what], (what, control[what])


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("what", sorted(TOL["moe"]))
def test_step_matches_float32_reference(case, what):
    check(case, what, CASES)


def test_expert_tally_counts_the_routing():
    prog, want, _ = run_case("moe", tuple(CASES["moe-1x1x1"][1].items()))
    tally, B = prog["tally"], 2
    assert tally.shape == (MOE_SIZES["n_layers"], MOE_SIZES["held_count"] + 1)
    assert (tally.sum(1) == B * SEQ * MOE_SIZES["top_k"]).all()
    gap = np.abs(tally[:, :-1] - want["counts"]).sum() / want["counts"].sum()
    assert gap < TALLY, gap


def test_float8_control_fails_the_logits_and_the_gradient():
    check_control("moe")
