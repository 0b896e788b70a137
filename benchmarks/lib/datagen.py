"""The one data generator: a table in the shape of the Criteo click logs,
made on the device from the seed, chunk by chunk.

A configuration's ``data`` block holds every parameter; nothing here names a
configuration. A chunk depends on (seed, chunk index, chunk rows) only, so
the driver that builds the resident dataset and the reference that replays it
afterwards see the same rows without either keeping them.

Fields: ``numeric`` count features ``floor(exp(N(log_mean, log_sigma)))`` with
a per-field missing share (NaN), then ``categorical`` id features drawn
log-uniform over the field's cardinality (Zipf s=1; the id is the popularity
rank, so the program's identity binning keeps the frequent ids apart and
buckets the tail in the last bin). The label is Bernoulli of a logit that is
linear in the standardized ``log1p`` numerics and in a fixed per-category
hash effect, so both numeric thresholds and category subsets carry signal.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """PRNG key for any non-negative ``--seed`` (they pass 2**31)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def feature_layout(data: dict):
    """(numeric count, categorical count, categorical column indexes)."""
    n_num = len(data["numeric"]["log_mean"])
    n_cat = len(data["categorical"]["cardinality"])
    return n_num, n_cat, tuple(range(n_num, n_num + n_cat))


def chunk_plan(rows: int, data: dict):
    """(chunk_rows, chunks) for ``rows``: the configured chunk where it
    tiles the table, one chunk for a toy table that it does not."""
    chunk = int(data["chunk_rows"])
    if rows % chunk:
        if rows > chunk:
            raise ValueError(f"rows={rows} is not a multiple of "
                             f"chunk_rows={chunk}")
        chunk = rows
    return chunk, rows // chunk


def _category_effect(ids, field: int):
    """Fixed effect in [-1, 1) of category ``ids`` of one field."""
    h = ids.astype(jnp.uint32) * jnp.uint32(2654435761) \
        + jnp.uint32((field * 40503 + 12345) & 0xFFFFFFFF)
    h = (h ^ (h >> jnp.uint32(15))) * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> jnp.uint32(12))
    return ((h >> jnp.uint32(8)) & jnp.uint32(0xFFFF)).astype(
        jnp.float32) / 32768.0 - 1.0


def gen_chunk(key, chunk_index, chunk_rows: int, data: dict):
    """Rows ``[chunk_index * chunk_rows, ...)``: ``(X [chunk_rows, F] f32,
    y [chunk_rows] f32)``. Traceable; ``chunk_index`` may be traced."""
    num, cat = data["numeric"], data["categorical"]
    n_num, n_cat, _ = feature_layout(data)
    k = jax.random.fold_in(key, chunk_index)
    kz, km, kc, ky = jax.random.split(k, 4)

    mu = jnp.asarray(num["log_mean"], jnp.float32)
    sg = jnp.asarray(num["log_sigma"], jnp.float32)
    z = jax.random.normal(kz, (chunk_rows, n_num), jnp.float32)
    counts = jnp.floor(jnp.exp(mu + sg * z))
    missing = jax.random.uniform(km, (chunk_rows, n_num)) < jnp.asarray(
        num["missing"], jnp.float32)
    x_num = jnp.where(missing, jnp.nan, counts)
    std = jnp.where(missing, 0.0, (jnp.log1p(counts) - mu) / sg)
    logit = data["label_bias"] + std @ jnp.asarray(num["label_weight"],
                                                  jnp.float32)

    log_card = jnp.log(jnp.asarray(cat["cardinality"], jnp.float32) + 1.0)
    u = jax.random.uniform(kc, (chunk_rows, n_cat))
    ids = jnp.clip(jnp.floor(jnp.exp(u * log_card)) - 1.0, 0.0,
                   jnp.asarray(cat["cardinality"], jnp.float32) - 1.0)
    for f, w in enumerate(cat["label_weight"]):
        if w:
            logit = logit + w * _category_effect(ids[:, f], f)

    y = (jax.random.uniform(ky, (chunk_rows,)) < jax.nn.sigmoid(logit))
    return (jnp.concatenate([x_num, ids], axis=1).astype(jnp.float32),
            y.astype(jnp.float32))


def sample_rows(key, count: int, chunk_rows: int, data: dict):
    """The first ``count`` rows of chunk 0 on the host: the binner's sample
    (rows are i.i.d., so a prefix is a fair one)."""
    count = min(int(count), chunk_rows)
    head = jax.jit(lambda k: gen_chunk(k, 0, chunk_rows, data)[0][:count])
    return np.asarray(head(key))
