"""Communication-schedule scaling pins on the COMPILED training step.

The reference's distributed-LightGBM scaling story rests on its histogram
all-reduce ring (reference: lightgbm/TrainUtils.scala:496-512 socket ring;
docs/lightgbm.md "linear speed-up"); the TPU-native equivalent is the
`psum` XLA inserts for the shard_map training step. These tests inspect
the ACTUAL optimized HLO the compiler emits (``--xla_dump_to``, run in a
subprocess because XLA_FLAGS is read at backend init) and pin the two
properties linear scaling rests on, independent of any timing:

1. the number of all-reduce sites in the compiled step does not grow
   with the shard count (fixed collective schedule);
2. every all-reduce payload is histogram/scalar-sized — O(F * B) — not
   data-sized, so the bytes crossing the interconnect are independent of
   both the row count and the shard count (weak scaling).
"""

import glob
import os
import re
import subprocess
import sys

import pytest

_PROBE = r"""
import os, sys
d = sys.argv[2]
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           f" --xla_dump_to={d}").strip()
import numpy as np, jax
from mmlspark_tpu.models.gbdt.booster import LightGBMDataset, train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig
from mmlspark_tpu.parallel import mesh as meshlib
nd = int(sys.argv[1])
rng = np.random.default_rng(0)
X = rng.normal(size=(2048, 8)).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
m = meshlib.make_mesh({"data": nd}, devices=jax.devices()[:nd])
with meshlib.default_mesh(m):
    ds = LightGBMDataset.construct(X, y, max_bin=31, mesh=m)
    train_booster(dataset=ds, num_iterations=2, objective="binary",
                  cfg=GrowConfig(num_leaves=7), mesh=m)
print("PROBE_DONE")
"""




def _collect_op(dump_dir, op):
    """[payload_elem_counts] of every `= <shape(s)> <op>(` site."""
    payloads = []
    for f in glob.glob(os.path.join(dump_dir, "*after_optimizations.txt")):
        for line in open(f):
            m = re.search(r"=\s+(.+?)\s+" + op + r"(?:-start)?\(", line)
            if not m:
                continue
            elems = 0
            for shape in re.finditer(r"\w+\[([0-9,]*)\]", m.group(1)):
                n = 1
                for p in shape.group(1).split(","):
                    if p:
                        n *= int(p)
                elems += n
            payloads.append(elems)
    return payloads


def _run_src(tmp_path, src, arg, tag, extra=()):
    dump = tmp_path / f"dump_{tag}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable, "-c", src, str(arg), str(dump)]
        + [str(e) for e in extra],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "PROBE_DONE" in r.stdout, r.stderr[-2000:]
    return str(dump)


@pytest.mark.slow
def test_allreduce_schedule_is_shard_count_invariant(tmp_path):
    payloads4 = _collect_op(_run_src(tmp_path, _PROBE, 4, "gbdt4"),
                            "all-reduce")
    payloads8 = _collect_op(_run_src(tmp_path, _PROBE, 8, "gbdt8"),
                            "all-reduce")
    sites4, sites8 = len(payloads4), len(payloads8)
    assert sites4 > 0, "distributed step emitted no collectives at all"
    # 1. fixed collective schedule: adding shards adds no sites
    assert sites4 == sites8, (sites4, sites8)
    # 2. identical payloads: the bytes on the wire don't grow with shards
    assert sorted(payloads4) == sorted(payloads8), (payloads4, payloads8)
    # 3. histogram-sized, not data-sized: every payload is bounded by a
    #    generous multiple of F*B (8 features x 32 bins here), far below
    #    the 2048x8 sharded data. This is the weak-scaling property: the
    #    interconnect carries histograms, never rows.
    F, B = 8, 32
    bound = 64 * F * B            # stat-axis/frontier multiplicity slack
    data_elems = 2048 * 8
    for p in payloads4:
        assert p <= bound, (p, bound)
        assert p < data_elems, (p, data_elems)


_VOTE_PROBE = r"""
import os, sys
d = sys.argv[2]
os.makedirs(d, exist_ok=True)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           f" --xla_dump_to={d}").strip()
import numpy as np, jax
from mmlspark_tpu.models.gbdt.booster import LightGBMDataset, train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig
from mmlspark_tpu.parallel import mesh as meshlib
voting = sys.argv[1] == "voting"
rng = np.random.default_rng(0)
X = rng.normal(size=(4096, 32)).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
m = meshlib.make_mesh({"data": 8}, devices=jax.devices()[:8])
with meshlib.default_mesh(m):
    ds = LightGBMDataset.construct(X, y, max_bin=31, mesh=m)
    train_booster(dataset=ds, num_iterations=2, objective="binary",
                  cfg=GrowConfig(num_leaves=7, voting=voting, top_k=2),
                  mesh=m)
print("PROBE_DONE")
"""

_RING_PROBE = r"""
import os, sys
d = sys.argv[2]
os.makedirs(d, exist_ok=True)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           f" --xla_dump_to={d}").strip()
import numpy as np, jax
from mmlspark_tpu.models.dnn.transformer import (
    TransformerConfig, adamw_init, init_params, make_train_step,
    shard_opt_state, shard_params)
from mmlspark_tpu.parallel.mesh import make_mesh
nd = int(sys.argv[1])
mesh = make_mesh({"data": 1, "seq": nd, "model": 1})
# deliberately tiny params vs long sequence: full-sequence activations
# (B*S*E = 16384 elems) dwarf the fused parameter-gradient all-reduce
# (~4.4k elems), so an activation-sized collective is unambiguously
# distinguishable from the legitimate param-grad sync
# 4 heads: ulysses reshards heads<->sequence, so heads must divide by
# the largest probed seq shard count (4); ring has no head constraint
cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=4, d_head=4,
                        n_layers=1, d_ff=32, max_len=512,
                        seq_attention=sys.argv[3])
params = shard_params(init_params(cfg, jax.random.PRNGKey(0)), cfg, mesh)
opt = shard_opt_state(adamw_init(params), cfg, mesh)
step = make_train_step(cfg, mesh, lr=1e-2)
rng = np.random.default_rng(0)
toks = rng.integers(0, 32, (2, 512)).astype(np.int32)
step(params, opt, toks, np.roll(toks, -1, 1))
print("PROBE_DONE")
"""


@pytest.mark.slow
def test_voting_parallel_shrinks_the_wire(tmp_path):
    """Voting's two-collective schedule (reference: LightGBM PV-Tree /
    LightGBMConstants DefaultTopK): a per-feature gain ballot plus only
    the 2k winning features' histograms must put FEWER elements on the
    interconnect than the dense full-width histogram psum."""
    dense = _collect_op(_run_src(tmp_path, _VOTE_PROBE, "dense", "dense"),
                        "all-reduce")
    voting = _collect_op(_run_src(tmp_path, _VOTE_PROBE, "voting", "vote"),
                         "all-reduce")
    assert dense and voting
    F, S, B = 32, 36, 31
    # dense ships at least one full-width [F, S, B] histogram
    assert max(dense) >= F * S * B
    # voting never ships a full-width histogram: ballots are F-sized and
    # winner histograms cover 2*top_k features out of F
    assert max(voting) < F * S * B
    assert sum(voting) < sum(dense) / 4


@pytest.mark.slow
def test_ring_attention_permutes_chunks_not_sequences(tmp_path):
    """Zig-zag ring attention's memory/communication contract: K/V blocks
    move between NEIGHBORS as chunk-sized collective-permutes whose
    payload shrinks as 1/seq_shards, and nothing ever all-gathers a
    full-sequence tensor (that would be the O(S) memory blowup sequence
    parallelism exists to avoid)."""
    d2 = _run_src(tmp_path, _RING_PROBE, 2, "ring2",
                  extra=["ring_zigzag"])
    d4 = _run_src(tmp_path, _RING_PROBE, 4, "ring4",
                  extra=["ring_zigzag"])
    p2 = _collect_op(d2, "collective-permute")
    p4 = _collect_op(d4, "collective-permute")
    assert p2 and p4
    # same schedule, half the chunk: site count invariant, payload halves
    assert len(p2) == len(p4), (p2, p4)
    assert sorted(p4) == [p // 2 for p in sorted(p2)], (p2, p4)
    # activation-MOVING collectives (permute/gather) never carry a
    # full-sequence tensor: the realistic sequence-parallel regression is
    # all-gathering K/V for full attention, and that trips both this
    # bound and the halving law above. The reduce family cannot get the
    # same absolute bound — the learned positional embedding's gradient
    # is a legitimate [max_len, E] param-grad psum, indistinguishable by
    # size from an activation — so reduces are pinned by volume
    # NON-GROWTH across shard counts instead (per-token loss terms
    # shrink with S_local; param grads stay constant).
    B, S, E = 2, 512, 16
    full_seq = B * S * E
    for d in (d2, d4):
        for op in ("collective-permute", "all-gather"):
            for p in _collect_op(d, op):
                # largest legitimate payload: one KV chunk at the minimum
                # shard count (full_seq / 2)
                assert p <= full_seq // 2, (op, p, full_seq)
    for op in ("all-reduce", "reduce-scatter", "all-to-all"):
        assert sum(_collect_op(d4, op)) <= sum(_collect_op(d2, op)), op


@pytest.mark.slow
def test_ulysses_alltoall_is_chunk_sized(tmp_path):
    """Ulysses reshards heads<->sequence with all-to-alls whose payload is
    the LOCAL activation chunk — it shrinks as 1/seq_shards like the ring
    permutes, never a gathered full sequence."""
    d2 = _run_src(tmp_path, _RING_PROBE, 2, "uly2", extra=["ulysses"])
    d4 = _run_src(tmp_path, _RING_PROBE, 4, "uly4", extra=["ulysses"])
    a2 = _collect_op(d2, "all-to-all")
    a4 = _collect_op(d4, "all-to-all")
    assert a2 and a4
    assert len(a2) == len(a4), (a2, a4)
    assert sorted(a4) == [p // 2 for p in sorted(a2)], (a2, a4)
    B, S, E = 2, 512, 16
    full_seq = B * S * E
    for d, payloads in ((d2, a2), (d4, a4)):
        for p in (payloads + _collect_op(d, "all-gather")
                  + _collect_op(d, "collective-permute")):
            assert p <= full_seq // 2, (p, full_seq)
    # same reduce-volume tail guard as the ring test: a full-sequence
    # leak through the reduce family must not hide behind intact
    # chunk-sized all-to-alls
    for op in ("all-reduce", "reduce-scatter"):
        assert sum(_collect_op(d4, op)) <= sum(_collect_op(d2, op)), op
