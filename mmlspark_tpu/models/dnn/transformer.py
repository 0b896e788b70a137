"""Flagship deep model: SPMD transformer with dp x tp x sp mesh parallelism.

This is the framework's DNN compute path — the TPU-native successor of the
reference's CNTK evaluation engine (reference: cntk/CNTKModel.scala:30-532
evaluates a serialized DNN per partition over JNI; no multi-device execution
of a single model existed — SURVEY.md §2b). Here a single model spans the
whole mesh:

  * ``data``  — batch sharding (DP)
  * ``model`` — Megatron-style tensor parallelism (TP): QKV/MLP column-split,
    output projections row-split with one psum per block
  * ``seq``   — sequence/context parallelism (SP): activations sharded over
    sequence; exact attention via ring ppermute (parallel/ring_attention.py).
    On one ``seq`` shard, with a sequence that is a multiple of 128, GPT-2's
    block runs the splash kernel instead (``_attention_kernel``)

Everything runs inside one ``shard_map``: collectives are explicit
(psum/pmax/ppermute) and ride ICI. Params live sharded (TP dims) or
replicated; gradients of replicated params are psum'd over (data, seq).
bf16 activations, f32 params/optimizer — the standard TPU recipe.

The block is GPT-2's unless the config describes another (``n_experts > 0``):
RMSNorm, a router read before attention, grouped-query attention whose
layers are each global (causal, no position encoding) or windowed (RoPE,
keys less than ``window`` positions back), and routed ReGLU experts of which
this device holds ``experts_held`` (``models/dnn/moe.py``). Attention runs
the splash kernel with whole blocks outside the mask skipped, and the loss
runs in token chunks. That block takes ``model = 1`` and ``seq = 1``: the
exchange of experts across chips and a ring schedule for windows are not
built, and a mesh that asks for them raises.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as splash_mask)
from jax.sharding import Mesh

from . import moe
from ...observability import metrics as _metrics
from ...parallel import placement
from ...parallel.placement import pspec as P
from ...parallel.ring_attention import (ring_attention,
                                        zigzag_ring_attention)
from ...parallel.compat import axis_size as compat_axis_size, shard_map
from ...parallel.ulysses import ulysses_attention


class TransformerConfig(NamedTuple):
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    d_head: int = 64
    n_layers: int = 4
    d_ff: int = 2048
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    # sequence-parallel attention schedule over a 'seq' mesh axis of more
    # than one shard: "ring" (neighbor ppermute, O(S_local) memory, no head
    # constraint), "ring_zigzag" (ring with the causally load-balanced
    # zig-zag layout — ~2x causal speedup; feed tokens/targets through
    # zigzag_permute), or "ulysses" (two all-to-alls reshard heads<->
    # sequence, plain local attention; needs per-TP-rank heads divisible by
    # the seq shard count). On one shard all three are plain local
    # attention, and the splash kernel runs whatever this says
    # (_attention_kernel)
    seq_attention: str = "ring"
    # Gradient rematerialization: recompute each block's activations in the
    # backward pass instead of storing them — activation memory drops from
    # O(n_layers * S_local * E) to O(S_local * E) at ~1/3 extra FLOPs, the
    # standard trade that lets long-context configs fit HBM. Exact to
    # numerical tolerance (XLA may fuse differently under checkpoint);
    # trajectory agreement is test-pinned.
    remat: bool = False
    # -- the described block (n_experts > 0); GPT-2's block ignores these --
    n_experts: int = 0            # router outputs; d_ff is an expert's width
    top_k: int = 0
    experts_held: Tuple[int, int] = (0, 0)   # (first, count) held here
    n_kv_heads: int = 0
    norm_eps: float = 1e-6        # RMSNorm
    rope_theta: float = 10000.0
    rope_layers: Tuple[int, ...] = ()        # per layer: 1 RoPE, 0 none
    windows: Tuple[int, ...] = ()            # per layer: 0 causal, else w
    loss_chunk: int = 0           # tokens a chunk of the head and loss


def init_params(cfg: TransformerConfig, key) -> Dict:
    """f32 parameters; layers stacked on a leading axis (scanned-friendly),
    or a list of per-layer dicts for the described block."""
    if cfg.n_experts:
        return _described_init(cfg, key)
    k_embed, k_pos, k_layers, k_head = jax.random.split(key, 4)
    E, H, Dh, F, L = (cfg.d_model, cfg.n_heads, cfg.d_head, cfg.d_ff,
                      cfg.n_layers)

    def norm(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    ks = jax.random.split(k_layers, 6 * L).reshape(L, 6, 2)
    layers = {
        "ln1_scale": jnp.ones((L, E)), "ln1_bias": jnp.zeros((L, E)),
        # [E, H, 3*Dh]: per-head q|k|v contiguous, so the head-axis TP shard
        # is layout-invariant across tensor-parallel sizes (checkpoint portable)
        "wqkv": jnp.stack([norm(ks[i, 0], (E, H, 3 * Dh), E ** -0.5)
                           for i in range(L)]),
        "wo": jnp.stack([norm(ks[i, 1], (H * Dh, E), (H * Dh) ** -0.5)
                         for i in range(L)]),
        "ln2_scale": jnp.ones((L, E)), "ln2_bias": jnp.zeros((L, E)),
        "w1": jnp.stack([norm(ks[i, 2], (E, F), E ** -0.5) for i in range(L)]),
        "b1": jnp.zeros((L, F)),
        "w2": jnp.stack([norm(ks[i, 3], (F, E), F ** -0.5) for i in range(L)]),
        "b2": jnp.zeros((L, E)),
    }
    return {
        "embed": norm(k_embed, (cfg.vocab_size, E), 1.0),
        "pos": norm(k_pos, (cfg.max_len, E), 0.02),
        "layers": layers,
        "lnf_scale": jnp.ones((E,)), "lnf_bias": jnp.zeros((E,)),
        "head": norm(k_head, (E, cfg.vocab_size), E ** -0.5),
    }


def param_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpecs mirroring init_params: TP dims sharded over 'model'."""
    if cfg.n_experts:
        return _described_specs(cfg)
    return {
        "embed": P(None, "model"),
        "pos": P(None, "model"),
        "layers": {
            "ln1_scale": P(None, None), "ln1_bias": P(None, None),
            "wqkv": P(None, None, "model", None),
            "wo": P(None, "model", None),
            "ln2_scale": P(None, None), "ln2_bias": P(None, None),
            "w1": P(None, None, "model"), "b1": P(None, "model"),
            "w2": P(None, "model", None), "b2": P(None, None),
        },
        "lnf_scale": P(None), "lnf_bias": P(None),
        "head": P(None, "model"),
    }


def _layer_norm(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + 1e-5) * scale + bias).astype(x.dtype)


_SEQ_SCHEDULES = {"ring": ring_attention,
                  "ring_zigzag": zigzag_ring_attention,
                  "ulysses": ulysses_attention}


def _attention_kernel(cfg: TransformerConfig, S: int) -> str:
    """What GPT-2's block computes attention with, from the ``seq`` shard
    count and the local sequence ``S``: the splash kernel on one shard
    where ``S`` is a multiple of 128, else ``cfg.seq_attention``'s
    schedule. Counted in ``lm_attention_kernel_total{kernel}``, once a
    build."""
    if cfg.seq_attention not in _SEQ_SCHEDULES:
        # all strategies are exact, so a typo would silently measure the
        # wrong one — fail loudly instead
        raise ValueError(
            f"unknown seq_attention {cfg.seq_attention!r}: "
            "use 'ring', 'ring_zigzag' or 'ulysses'")
    one_shard = compat_axis_size("seq") == 1 and S % 128 == 0
    kernel = "splash" if one_shard else cfg.seq_attention
    _metrics.safe_counter("lm_attention_kernel_total", kernel=kernel).inc()
    return kernel


def _attend(q, k, v, kernel: str, causal: bool):
    """Attention of ``[B, H, S, D]`` q, k, v by ``kernel`` (a name
    :func:`_attention_kernel` gives); q comes scaled where it is
    ``"splash"``."""
    if kernel != "splash":
        return _SEQ_SCHEDULES[kernel](q, k, v, axis_name="seq",
                                      causal=causal)
    splash_mha = _splash_kernel(q.shape[2], q.shape[1], 0, causal)
    return jax.vmap(splash_mha)(q, k, v)


def forward_local(params, tokens, cfg: TransformerConfig,
                  causal: bool = True):
    """Local-shard forward inside shard_map; returns vocab-sharded logits.

    tokens: [B_local, S_local] int32. Axes: data/seq/model as module docstring.
    """
    H, Dh, E = cfg.n_heads, cfg.d_head, cfg.d_model
    tp = compat_axis_size("model")
    sp_idx = lax.axis_index("seq")
    Hl = H // tp
    B, S = tokens.shape
    dt = cfg.dtype
    kernel = _attention_kernel(cfg, S)

    # embedding: table is E-sharded; gather rows then all-gather E
    emb_local = jnp.take(params["embed"], tokens, axis=0)  # [B, S, E/tp]
    if cfg.seq_attention == "ring_zigzag":
        # zig-zag layout: this shard holds chunk me and chunk 2n-1-me of
        # the global sequence (tokens/targets must be pre-permuted with
        # parallel.ring_attention.zigzag_permute) — slice the positional
        # table accordingly
        n_sp = compat_axis_size("seq")
        C = S // 2
        p1 = lax.dynamic_slice_in_dim(params["pos"], sp_idx * C, C, axis=0)
        p2 = lax.dynamic_slice_in_dim(
            params["pos"], (2 * n_sp - 1 - sp_idx) * C, C, axis=0)
        pos_local = jnp.concatenate([p1, p2], axis=0)
    else:
        pos_local = lax.dynamic_slice_in_dim(params["pos"], sp_idx * S, S,
                                             axis=0)
    x_local = emb_local + pos_local[None]
    x = lax.all_gather(x_local, "model", axis=2, tiled=True).astype(dt)  # [B,S,E]

    def block(x, lp):
        h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
        with jax.named_scope("attn"):
            qkv = jnp.einsum(                          # [B, S, Hl, 3*Dh]
                "bse,ehk->bshk", h, lp["wqkv"].astype(dt),
                preferred_element_type=jnp.float32)
            qkv = qkv.reshape(B, S, Hl, 3, Dh)
            if kernel == "splash":
                # the kernel takes q scaled: scaled in f32, rounded once
                qkv = qkv * jnp.asarray([Dh ** -0.5, 1.0, 1.0],
                                        jnp.float32)[:, None]
            qkv = qkv.astype(dt)
            q = qkv[:, :, :, 0].transpose(0, 2, 1, 3)  # [B, Hl, S, Dh]
            k = qkv[:, :, :, 1].transpose(0, 2, 1, 3)
            v = qkv[:, :, :, 2].transpose(0, 2, 1, 3)
            att = _attend(q, k, v, kernel, causal)
            att = att.transpose(0, 2, 1, 3).reshape(B, S, Hl * Dh)
            out = jnp.einsum("bsk,ke->bse", att, lp["wo"].astype(dt),
                             preferred_element_type=jnp.float32)
            out = lax.psum(out, "model").astype(dt)
        x = x + out
        h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
        m = jnp.einsum("bse,ef->bsf", h, lp["w1"].astype(dt),
                       preferred_element_type=jnp.float32) + lp["b1"]
        m = jax.nn.gelu(m.astype(jnp.float32)).astype(dt)
        m = jnp.einsum("bsf,fe->bse", m, lp["w2"].astype(dt),
                       preferred_element_type=jnp.float32)
        m = lax.psum(m, "model").astype(dt) + lp["b2"].astype(dt)
        return x + m, None

    # prevent_cse=False: safe and recommended when the checkpointed fn is a
    # lax.scan body (per jax.checkpoint docs) — keeps XLA's CSE instead of
    # paying optimization-barrier overhead on every step
    x, _ = lax.scan(jax.checkpoint(block, prevent_cse=False)
                    if cfg.remat else block,
                    x, params["layers"])
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    logits_local = jnp.einsum("bse,ev->bsv", x, params["head"].astype(dt),
                              preferred_element_type=jnp.float32)
    return logits_local  # [B, S, V/tp] f32


def sharded_xent(logits_local, targets, cfg: TransformerConfig):
    """Cross-entropy over vocab-sharded logits (stable log-sum-exp with
    pmax/psum over 'model'); mean over all tokens via pmean over data x seq."""
    tp = compat_axis_size("model")
    v_local = cfg.vocab_size // tp
    v0 = lax.axis_index("model") * v_local
    # stability shift only — constant w.r.t. differentiation (pmax has no JVP,
    # so stop the gradient BEFORE it enters the collective)
    lmax = lax.pmax(lax.stop_gradient(logits_local.max(-1)), "model")
    z = jnp.exp(logits_local - lmax[..., None])
    log_z = jnp.log(lax.psum(z.sum(-1), "model")) + lmax
    t_local = targets - v0
    in_range = (t_local >= 0) & (t_local < v_local)
    picked = jnp.take_along_axis(
        logits_local, jnp.clip(t_local, 0, v_local - 1)[..., None], axis=-1)[..., 0]
    picked = lax.psum(jnp.where(in_range, picked, 0.0), "model")
    nll = log_z - picked
    return lax.pmean(lax.pmean(nll.mean(), "data"), "seq")


# ---------------------------------------------------------------------------
# the described block: RMSNorm, GQA with per-layer windows and RoPE, experts
# ---------------------------------------------------------------------------


def _described_init(cfg: TransformerConfig, key) -> Dict:
    E, H, Dh, F, V = (cfg.d_model, cfg.n_heads, cfg.d_head, cfg.d_ff,
                      cfg.vocab_size)
    Hk, held = cfg.n_kv_heads or H, cfg.experts_held[1]
    shapes = {"wq": ((E, H, Dh), E), "wk": ((E, Hk, Dh), E),
              "wv": ((E, Hk, Dh), E), "wo": ((H, Dh, E), H * Dh),
              "router": ((E, cfg.n_experts), E), "w1": ((held, E, F), E),
              "w3": ((held, E, F), E), "w2": ((held, F, E), F)}
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for k_layer in jax.random.split(k_layers, cfg.n_layers):
        ks = jax.random.split(k_layer, len(shapes))
        layer = {name: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5
                 for k, (name, (shape, fan)) in zip(ks, shapes.items())}
        layer.update(norm1=jnp.ones((E,)), norm2=jnp.ones((E,)))
        layers.append(layer)
    return {"embed": jax.random.normal(k_embed, (V, E), jnp.float32),
            "layers": layers, "norm_f": jnp.ones((E,)),
            "head": jax.random.normal(k_head, (E, V), jnp.float32)
            * E ** -0.5}


def _described_specs(cfg: TransformerConfig) -> Dict:
    """Replicated, but for the head, which is vocabulary-sharded as
    :func:`sharded_xent` takes it (over a one-wide ``model`` axis)."""
    layer = {k: P() for k in ("norm1", "wq", "wk", "wv", "wo", "router",
                              "norm2", "w1", "w3", "w2")}
    return {"embed": P(), "layers": [dict(layer)
                                     for _ in range(cfg.n_layers)],
            "norm_f": P(), "head": P(None, "model")}


def _check_mesh(cfg: TransformerConfig, mesh: Mesh) -> None:
    """The limits of the described block's mesh, named."""
    if not cfg.n_experts:
        return
    sizes = dict(mesh.shape)
    tp, sp = sizes.get("model", 1), sizes.get("seq", 1)
    kv = cfg.n_kv_heads or cfg.n_heads
    if kv % tp:
        raise ValueError(f"{kv} K/V heads do not divide over model = {tp}")
    if tp > 1:
        raise ValueError(f"routed experts take model = 1, got {tp}: their "
                         "exchange across chips is not built")
    if sp > 1:
        raise ValueError(f"the described block's attention takes seq = 1, "
                         f"got {sp}: no ring schedule runs windows or "
                         "grouped K/V heads")


def layer_plan(cfg: TransformerConfig) -> Tuple[Tuple[int, bool], ...]:
    """``(window, rope)`` of each layer: window 0 is full causal."""
    windows = cfg.windows or (0,) * cfg.n_layers
    ropes = cfg.rope_layers or (0,) * cfg.n_layers
    if len(windows) != cfg.n_layers or len(ropes) != cfg.n_layers:
        raise ValueError(f"windows and rope_layers need {cfg.n_layers} "
                         f"entries, got {len(windows)} and {len(ropes)}")
    return tuple((int(w), bool(r)) for w, r in zip(windows, ropes))


def _rms_norm(x, scale, eps):
    """RMSNorm in f32; f32 out."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over all of ``x``'s last axis, positions ``0..S-1``
    along its second-to-last; f32 in, f32 out."""
    S, D = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    angle = jnp.concatenate([angle, angle], axis=-1)              # [S, D]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


@functools.lru_cache(maxsize=None)
def _splash_kernel(S: int, H: int, window: int = 0, causal: bool = True, *,
                   block: int | None = None, interpret: bool | None = None):
    """Splash attention over ``H`` query heads (K/V heads grouped), causal
    or, with ``window``, keys ``0 <= i - j < window``, or without
    ``causal`` every key; whole square blocks outside the mask are
    skipped. ``block`` defaults to ``min(512, S)`` where that divides
    ``S``, else 256 or 128. ``interpret`` defaults to ``moe.interpret()``,
    the one switch of every Pallas kernel the DNN path runs (set it before
    the first build: the kernel is cached)."""
    if S % 128:
        raise ValueError(f"splash attention needs a sequence that is a "
                         f"multiple of 128, got {S}")
    b = block or next(b for b in (min(512, S), 256, 128) if S % b == 0)
    if interpret is None:
        interpret = moe.interpret()
    if window:
        mask = splash_mask.LocalMask((S, S), (window - 1, 0), 0)
    elif causal:
        mask = splash_mask.CausalMask((S, S))
    else:
        mask = splash_mask.FullMask((S, S))
    with jax.ensure_compile_time_eval():     # concrete tables: it is cached
        return splash.make_splash_mha(
            splash_mask.MultiHeadMask([mask] * H), head_shards=1,
            q_seq_shards=1, interpret=interpret,
            block_sizes=splash.BlockSizes(
                block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
                block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b,
                block_kv_dq=b))


def _attention(h, lp, cfg: TransformerConfig, window: int, rope: bool):
    """Grouped-query attention of ``h`` ``[B, S, E]``; f32 ``[B, S, E]``."""
    dt, S = cfg.dtype, h.shape[1]
    with jax.named_scope("attn_window" if window else "attn_global"):
        q, k, v = (jnp.einsum("bse,ehd->bhsd", h, lp[n].astype(dt),
                              preferred_element_type=jnp.float32)
                   for n in ("wq", "wk", "wv"))
        q = q * cfg.d_head ** -0.5
        if rope:
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        kernel = _splash_kernel(S, cfg.n_heads, window)
        o = jax.vmap(kernel)(q.astype(dt), k.astype(dt), v.astype(dt))
        return jnp.einsum("bhsd,hde->bse", o, lp["wo"].astype(dt),
                          preferred_element_type=jnp.float32)


def _described_layer(x, lp, cfg: TransformerConfig, window: int,
                     rope: bool):
    """One layer: the router reads the normed input before attention, the
    experts the normed residual after it. Returns ``(x, tally)``."""
    B, S, E = x.shape
    dt = cfg.dtype
    h = _rms_norm(x, lp["norm1"], cfg.norm_eps)
    ids, weights = moe.route(h.reshape(B * S, E), lp["router"], cfg.top_k)
    x = x + _attention(h.astype(dt), lp, cfg, window, rope).astype(dt)
    u = _rms_norm(x, lp["norm2"], cfg.norm_eps).astype(dt)
    y, tally = moe.experts(u.reshape(B * S, E), ids, weights,
                           lp["w1"].astype(dt), lp["w3"].astype(dt),
                           lp["w2"].astype(dt), cfg.experts_held)
    return x + y.reshape(B, S, E).astype(dt), tally


def described_forward_local(params, tokens, cfg: TransformerConfig):
    """The described block's final normed hidden state ``[B, S, E]`` and
    its expert tally ``[L, held + 1]``, inside shard_map."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    tallies = []
    for lp, (window, rope) in zip(params["layers"], layer_plan(cfg)):
        layer = functools.partial(_described_layer, cfg=cfg, window=window,
                                  rope=rope)
        x, tally = (jax.checkpoint(layer) if cfg.remat else layer)(x, lp)
        tallies.append(tally)
    return (_rms_norm(x, params["norm_f"], cfg.norm_eps).astype(cfg.dtype),
            jnp.stack(tallies))


def described_loss(params, tokens, targets, cfg: TransformerConfig):
    """``(mean cross-entropy, tally)``: the head and :func:`sharded_xent` run
    ``cfg.loss_chunk`` tokens at a time, each chunk's logits recomputed for
    its backward pass, so that no ``[tokens, vocab]`` array is whole."""
    x, tally = described_forward_local(params, tokens, cfg)
    B, S, E = x.shape
    chunk = cfg.loss_chunk or B * S
    if (B * S) % chunk:
        raise ValueError(f"loss_chunk {chunk} does not divide {B * S} "
                         "tokens")
    n = B * S // chunk
    head = params["head"].astype(cfg.dtype)

    def one(total, part):
        xc, tc = part
        logits = jnp.einsum("ce,ev->cv", xc, head,
                            preferred_element_type=jnp.float32)
        return total + sharded_xent(logits[None], tc[None], cfg), None

    with jax.named_scope("chunked_loss"):
        total, _ = lax.scan(jax.checkpoint(one), jnp.float32(0),
                            (x.reshape(n, chunk, E),
                             targets.reshape(n, chunk)))
    return total / n, tally


def expert_tally(opt_state) -> np.ndarray:
    """The assignments counted since :func:`adamw_init`, ``[L, held + 1]``
    int: to each held expert of each layer and, last, to absent ones. One
    read from the device."""
    return np.asarray(jax.device_get(opt_state["tally"]))


# ---------------------------------------------------------------------------
# hand-rolled AdamW (full sharding control over optimizer state)
# ---------------------------------------------------------------------------


def adamw_init(params, cfg: Optional[TransformerConfig] = None):
    """AdamW's state; for the described block also the expert tally, which
    the step accumulates (:func:`expert_tally`)."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = {"mu": zeros,
             "nu": jax.tree_util.tree_map(jnp.zeros_like, params),
             "count": jnp.zeros((), jnp.int32)}
    if cfg is not None and cfg.n_experts:
        state["tally"] = jnp.zeros(
            (cfg.n_layers, cfg.experts_held[1] + 1), jnp.int32)
    return state


def adamw_update(grads, state, params, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.01):
    c = state["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                state["nu"], grads)
    cf = c.astype(jnp.float32)
    bc1 = 1 - b1 ** cf
    bc2 = 1 - b2 ** cf

    def upd(p, m, n):
        return p - lr * (m / bc1 / (jnp.sqrt(n / bc2) + eps) + weight_decay * p)

    new_params = jax.tree_util.tree_map(upd, params, mu, nu)
    return new_params, {"mu": mu, "nu": nu, "count": c}


# ---------------------------------------------------------------------------
# jit-able train / forward steps over a mesh
# ---------------------------------------------------------------------------


def make_train_step(cfg: TransformerConfig, mesh: Mesh, lr: float = 1e-3):
    """Returns jitted (params, opt_state, tokens, targets) -> (params, opt_state, loss).

    Replicated-param gradients are psum'd over (data, seq); TP-sharded params
    update locally. One compiled SPMD program, collectives over ICI.
    """
    specs = param_specs(cfg)

    _check_mesh(cfg, mesh)

    def step_local(params, opt_state, tokens, targets):
        def loss_fn(p):
            logits = forward_local(p, tokens, cfg)
            return sharded_xent(logits, targets, cfg)

        if cfg.n_experts:
            (loss, tally), grads = jax.value_and_grad(
                described_loss, has_aux=True)(params, tokens, targets, cfg)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params)
        tp = compat_axis_size("model")
        if tp > 1:
            # the loss is the same on every 'model' shard, and each psum of
            # sharded_xent transposes to a psum: the gradient comes back tp
            # times over
            grads = jax.tree_util.tree_map(lambda g: g / tp, grads)
        # TP-sharded params get complete local grads (loss aggregates over
        # 'model' via psum in the forward); params REPLICATED across 'model'
        # (layernorms, b2) only get partial contributions per shard — sum them
        # or the replicas silently diverge.
        grads = jax.tree_util.tree_map(
            lambda g, s: g if "model" in tuple(s) else lax.psum(g, "model"),
            grads, specs)
        # all params are replicated across data & seq: average contributions
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(lax.pmean(g, "data"), "seq"), grads)
        new_params, new_opt = adamw_update(grads, opt_state, params, lr=lr)
        if cfg.n_experts:
            new_opt["tally"] = opt_state["tally"] + lax.psum(
                tally, ("data", "seq"))
        return new_params, new_opt, loss

    opt_specs = _opt_specs(cfg)
    data_spec = P("data", "seq")
    fn = shard_map(
        step_local, mesh=mesh,
        in_specs=(specs, opt_specs, data_spec, data_spec),
        out_specs=(specs, opt_specs, P()),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1))


def make_forward(cfg: TransformerConfig, mesh: Mesh, causal: bool = True):
    """Jitted forward: (params, tokens [B, S]) -> full logits [B, S, V]."""
    specs = param_specs(cfg)
    _check_mesh(cfg, mesh)

    def fwd_local(params, tokens):
        if cfg.n_experts:
            x, _ = described_forward_local(params, tokens, cfg)
            return jnp.einsum("bse,ev->bsv", x, params["head"].astype(
                cfg.dtype), preferred_element_type=jnp.float32)
        logits_local = forward_local(params, tokens, cfg, causal=causal)
        return logits_local

    fn = shard_map(
        fwd_local, mesh=mesh,
        in_specs=(specs, P("data", "seq")),
        out_specs=P("data", "seq", "model"),
        check_vma=False)
    return jax.jit(fn)


def shard_params(params, cfg: TransformerConfig, mesh: Mesh):
    placement.plan_for("transformer.fit", mesh=mesh, what="params_tp")
    return placement.put_tree(params, param_specs(cfg), mesh)


def shard_opt_state(opt_state, cfg: TransformerConfig, mesh: Mesh):
    return placement.put_tree(opt_state, _opt_specs(cfg), mesh)


def _opt_specs(cfg: TransformerConfig) -> Dict:
    specs = {"mu": param_specs(cfg), "nu": param_specs(cfg), "count": P()}
    if cfg.n_experts:
        specs["tally"] = P()
    return specs
