"""Seeded-violation corpus for graftlint.

Every rule is fed a known-bad snippet (a mutated copy of the original
offending pattern its ``tests/test_lint.py`` ancestor guarded against)
and must report the exact rule id at the exact file:line — plus a
suppressed variant proving ``# graftlint: disable=<rule>`` works. This
is the regression harness for the port: a guard that silently stopped
matching its original bad pattern fails here, not in production review.

Infrastructure tests (CLI exit codes, JSON shape, lint-rot conversion,
file-level suppression, the env-docs generator) ride along at the end.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.graftlint import core  # noqa: E402

core.load_checkers()


def run_rule(tmp_path, rule, files):
    """Write ``files`` (rel -> source) under ``tmp_path``, run one rule,
    return (active, suppressed) findings."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    repo = core.Repo(str(tmp_path))
    return core.run(repo, rules=[rule])


def hits(findings, rule, path=None):
    return [f for f in findings if f.rule == rule
            and (path is None or f.path == path)]


# --------------------------------------------------------------------------
# shared anchor fragments (each rule's checker refuses to run without the
# real code it guards — seeds must reproduce those anchors)
# --------------------------------------------------------------------------

OBS_LOGGING = """\
    def get_logger(name):
        return name

    def console(msg):
        import sys
        sys.stderr.write(msg)
"""

IO_SERVING = """\
    def write_http_response(handler, status):
        handler.send_response(status)
"""

STREAMING_CLEAN = """\
    def stream_apply(chunks, fn):
        out = []
        for c in chunks:
            out.append(fn(c))
        return out
"""

BEAT_LOOPS_CLEAN = """\
    def run_loop(hb, items, work):
        for it in items:
            hb.beat()
            work(it)

    def run_loop2(hb, items, work):
        while items:
            hb.beat()
            work(items.pop())
"""


# --------------------------------------------------------------------------
# funnel rules
# --------------------------------------------------------------------------

class TestFunnelRules:
    def test_raw_output(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "raw-output-funnel", {
            "mmlspark_tpu/observability/logging.py": OBS_LOGGING,
            "mmlspark_tpu/worker.py": """\
                import sys

                def f():
                    print("hi")
                    sys.stderr.write("x")
                    print("ok")  # graftlint: disable=raw-output-funnel (test)
            """})
        got = hits(active, "raw-output-funnel", "mmlspark_tpu/worker.py")
        assert [(f.line) for f in got] == [4, 5], active
        assert [f.line for f in suppressed] == [6]

    def test_stdlib_getlogger(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "stdlib-getlogger", {
            "mmlspark_tpu/observability/logging.py": OBS_LOGGING,
            "mmlspark_tpu/worker.py": """\
                import logging

                log = logging.getLogger(__name__)
                ok = logging.getLogger("x")  # graftlint: disable=stdlib-getlogger (test)
            """})
        assert [f.line for f in
                hits(active, "stdlib-getlogger",
                     "mmlspark_tpu/worker.py")] == [3]
        assert [f.line for f in suppressed] == [4]

    def test_send_response(self, tmp_path):
        active, _sup = run_rule(tmp_path, "response-funnel", {
            "mmlspark_tpu/io/serving.py": IO_SERVING,
            "mmlspark_tpu/io/handler.py": """\
                class H:
                    def do_GET(self):
                        self.send_response(200)
            """})
        got = hits(active, "response-funnel", "mmlspark_tpu/io/handler.py")
        assert [f.line for f in got] == [3], active
        # the funnel function itself is sanctioned
        assert not hits(active, "response-funnel",
                        "mmlspark_tpu/io/serving.py")

    def test_shard_map(self, tmp_path):
        active, _sup = run_rule(tmp_path, "shard-map-funnel", {
            "mmlspark_tpu/parallel/compat.py": "def shard_map():\n    pass\n",
            "mmlspark_tpu/mesh_user.py": """\
                import jax
                from jax.experimental.shard_map import shard_map

                def f(g):
                    return jax.shard_map(g)
            """,
            "tests/test_seeded.py": """\
                import jax

                def check(g):
                    return jax.shard_map(g)
            """})
        assert [f.line for f in
                hits(active, "shard-map-funnel",
                     "mmlspark_tpu/mesh_user.py")] == [2, 5]
        # tests/ are in scope: the funnel guards the whole repo
        assert [f.line for f in
                hits(active, "shard-map-funnel",
                     "tests/test_seeded.py")] == [4]

    def test_trace_header_literal(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "trace-header-literal", {
            "mmlspark_tpu/observability/tracing.py":
                'TRACEPARENT_HEADER = "traceparent"\n',
            "mmlspark_tpu/io/hop.py": """\
                H = "traceparent"
                R = "X-Request-Id"
                OK = "x-request-id"  # graftlint: disable=trace-header-literal (test)
            """})
        got = hits(active, "trace-header-literal", "mmlspark_tpu/io/hop.py")
        assert [f.line for f in got] == [1, 2], active
        assert [f.line for f in suppressed] == [3]

    def test_deadline_header_literal(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "deadline-header-literal", {
            "mmlspark_tpu/robustness/policy.py":
                'DEADLINE_HEADER = "X-Deadline-Ms"\n',
            "mmlspark_tpu/io/hop.py": """\
                H = "X-Deadline-Ms"
                L = "x-deadline-ms"
                OK = "x-deadline-ms"  # graftlint: disable=deadline-header-literal (test)
            """})
        got = hits(active, "deadline-header-literal",
                   "mmlspark_tpu/io/hop.py")
        assert [f.line for f in got] == [1, 2], active
        assert [f.line for f in suppressed] == [3]
        # the defining module is sanctioned
        assert not hits(active, "deadline-header-literal",
                        "mmlspark_tpu/robustness/policy.py")

    def test_placement_funnel(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "placement-funnel", {
            "mmlspark_tpu/parallel/placement.py":
                "def pspec(*entries):\n    return entries\n",
            "mmlspark_tpu/parallel/compat.py": """\
                import jax

                def put(x):
                    return jax.device_put(x)   # allowlisted module
            """,
            "mmlspark_tpu/models/rogue.py": """\
                import jax
                from jax.sharding import Mesh, NamedSharding
                from jax import device_put

                def put(x, mesh, spec):
                    import jax.sharding
                    sh = jax.sharding.PartitionSpec("data")
                    out = jax.device_put(x, NamedSharding(mesh, sh))
                    ok = jax.device_put(x)  # graftlint: disable=placement-funnel (test)
                    return out, sh, ok, device_put
            """})
        got = hits(active, "placement-funnel", "mmlspark_tpu/models/rogue.py")
        # the Mesh import is legal (topology, not placement); the
        # NamedSharding / bare-device_put / module imports, the
        # jax.sharding.PartitionSpec attribute and jax.device_put are not
        assert [f.line for f in got] == [2, 3, 6, 7, 8], active
        assert [f.line for f in suppressed] == [9]
        assert not hits(active, "placement-funnel",
                        "mmlspark_tpu/parallel/compat.py")

    def test_bundle_io_funnel(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "bundle-io-funnel", {
            "mmlspark_tpu/bundles/bundle.py": """\
                def build_bundle(model_path, out_dir):
                    from jax import export as jax_export   # the funnel
                    return jax_export
            """,
            "mmlspark_tpu/io/rogue.py": """\
                import jax
                import jax.export
                from jax import export
                from jax.export import deserialize

                def load(blob):
                    exp = jax.export.deserialize(blob)
                    ok = jax.export  # graftlint: disable=bundle-io-funnel (test)
                    return exp, ok
            """})
        got = hits(active, "bundle-io-funnel", "mmlspark_tpu/io/rogue.py")
        # the module import, both from-imports, and the attribute touch
        # all flag; the plain `import jax` does not
        assert [f.line for f in got] == [2, 3, 4, 7], active
        assert [f.line for f in suppressed] == [8]
        # the bundles package is the sanctioned owner
        assert not hits(active, "bundle-io-funnel",
                        "mmlspark_tpu/bundles/bundle.py")

    def test_retry_sleep_funnel(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "retry-sleep-funnel", {
            "mmlspark_tpu/robustness/policy.py":
                "def backoff(attempt):\n    pass\n",
            "mmlspark_tpu/io/client.py": """\
                import time

                def fetch(send):
                    for attempt in range(3):
                        resp = send()
                        if resp:
                            return resp
                        time.sleep(2 ** attempt)

                def poll(ready):
                    while not ready():
                        time.sleep(0.1)  # graftlint: disable=retry-sleep-funnel (test)

                def one_shot():
                    time.sleep(0.5)      # not in a loop: out of scope
            """,
            "mmlspark_tpu/models/trainer.py": """\
                import time

                def wait():
                    while True:
                        time.sleep(1.0)
            """})
        got = hits(active, "retry-sleep-funnel",
                   "mmlspark_tpu/io/client.py")
        assert [f.line for f in got] == [8], active
        assert [f.line for f in suppressed] == [12]
        # the rule scopes io/ only — a training-loop sleep is not a
        # retry-path concern
        assert not hits(active, "retry-sleep-funnel",
                        "mmlspark_tpu/models/trainer.py")

    def test_tuning_store_funnel(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "tuning-store-funnel", {
            "mmlspark_tpu/tuning/store.py": """\
                def load_store(dirpath):
                    return {}

                def save_store(dirpath, payload):
                    name = "tuning.json"
                    return name
            """,
            "mmlspark_tpu/tuning/__init__.py": """\
                from .store import load_store, save_store

                def resolve_bucket_ladder():
                    return load_store("/tmp")
            """,
            "mmlspark_tpu/io/rogue.py": """\
                import json
                import os

                def peek(dirpath):
                    path = os.path.join(dirpath, "tuning.json")
                    with open(path) as fh:
                        return json.load(fh)

                def rewrite(dirpath, payload):
                    save_store(dirpath, payload)
                    ok = load_store(dirpath)  # graftlint: disable=tuning-store-funnel (test)
                    return ok
            """})
        got = hits(active, "tuning-store-funnel", "mmlspark_tpu/io/rogue.py")
        assert [f.line for f in got] == [5, 10], active
        assert "tuning.json" in got[0].message
        assert "save_store(" in got[1].message
        assert [f.line for f in suppressed] == [11]
        # the tuning package is the sanctioned owner of the store
        assert not hits(active, "tuning-store-funnel",
                        "mmlspark_tpu/tuning/store.py")
        assert not hits(active, "tuning-store-funnel",
                        "mmlspark_tpu/tuning/__init__.py")


# --------------------------------------------------------------------------
# metric rules
# --------------------------------------------------------------------------

_TEN_GOOD_METRICS = "\n".join(
    f'    counter("good_{w}_total").inc()'
    for w in ("a", "b", "c", "d", "e", "f", "g", "h", "i", "j"))


class TestMetricRules:
    def test_name_format(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "metric-name-format", {
            "mmlspark_tpu/wiring.py": (
                "def wire(counter):\n" + _TEN_GOOD_METRICS + "\n"
                '    counter("Bad-Name").inc()\n'
                '    counter("also.bad").inc()'
                '  # graftlint: disable=metric-name-format (test)\n')})
        got = hits(active, "metric-name-format")
        assert [f.line for f in got] == [12], active
        assert "Bad-Name" in got[0].message
        assert len(suppressed) == 1

    def test_kind_unique(self, tmp_path):
        active, _sup = run_rule(tmp_path, "metric-kind-unique", {
            "mmlspark_tpu/wiring.py": """\
                def wire(counter, gauge, safe_counter):
                    counter("dup_total").inc()
                    safe_counter("dup_total").inc()     # same kind: fine
                    gauge("dup_total").set(1.0)         # kind conflict
            """})
        got = hits(active, "metric-kind-unique")
        assert [f.line for f in got] == [4], active
        assert "dup_total" in got[0].message


# --------------------------------------------------------------------------
# import-cycle rule
# --------------------------------------------------------------------------

def test_obs_import_cycle(tmp_path):
    active, suppressed = run_rule(tmp_path, "obs-import-cycle", {
        "mmlspark_tpu/observability/metrics.py": "enabled = lambda: True\n",
        "mmlspark_tpu/observability/bad.py": """\
            import os
            from mmlspark_tpu import core
            from ..io import serving
            from .metrics import enabled
            from .weird import x
            from .flight import record  # graftlint: disable=obs-import-cycle (not a violation, proves line-suppression keys on the import line)

            def lazy():
                from ..models import gbdt   # deferred: legal
        """})
    got = hits(active, "obs-import-cycle",
               "mmlspark_tpu/observability/bad.py")
    assert [f.line for f in got] == [2, 3, 5], active


# --------------------------------------------------------------------------
# hot-path-host-sync
# --------------------------------------------------------------------------

class TestAsyncBlockingCall:
    def test_blocking_calls_in_async_def(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "async-blocking-call", {
            "mmlspark_tpu/io/aserve/server.py": """\
                import queue
                import socket
                import time

                import requests


                async def handle(conn, q):
                    time.sleep(0.1)
                    requests.get("http://x")
                    sock = socket.create_connection(("x", 80))
                    data = sock.recv(4096)
                    item = q.get()
                    item2 = q.get(timeout=1.0)
                    ok = q.get(timeout=1.0)  # graftlint: disable=async-blocking-call (test)
                    return data, item, item2, ok
            """})
        got = hits(active, "async-blocking-call",
                   "mmlspark_tpu/io/aserve/server.py")
        assert [f.line for f in got] == [9, 10, 11, 12, 13, 14], active
        assert [f.line for f in suppressed] == [15]

    def test_sync_code_and_nested_defs_exempt(self, tmp_path):
        active, _sup = run_rule(tmp_path, "async-blocking-call", {
            "mmlspark_tpu/io/aserve/server.py": """\
                import asyncio
                import os
                import time


                def plain(q):
                    # sync function: blocking is its business
                    time.sleep(0.1)
                    return q.get()


                async def handler(loop, q, headers):
                    # nested sync helper runs where it's CALLED (a worker
                    # thread via to_thread) — not on the loop
                    def pull():
                        return q.get(timeout=1.0)

                    item = await asyncio.to_thread(pull)
                    # keyed mapping lookups are not queue reads
                    val = headers.get("content-length")
                    env = os.environ.get("HOME", "/")
                    await asyncio.sleep(0)
                    return item, val, env
            """})
        assert not active, active

    def test_rots_without_async_defs(self, tmp_path):
        active, _sup = run_rule(tmp_path, "async-blocking-call", {
            "mmlspark_tpu/plain.py": "def f():\n    return 1\n"})
        rot = hits(active, "async-blocking-call")
        assert len(rot) == 1 and "lint-rot" in rot[0].message, active


class TestHotPathHostSync:
    def test_streaming_chunk_loop(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "hot-path-host-sync", {
            "mmlspark_tpu/io/streaming.py": """\
                import numpy as np
                from numpy import asarray

                def stream_apply(chunks, fn):
                    out = []
                    for c in chunks:
                        out.append(np.asarray(fn(c)))
                        x = float(c)
                        z = asarray(c)
                        y = np.asarray(c)  # graftlint: disable=hot-path-host-sync (test)
                    return out

                def helper_outside_is_legal(chunks, score):
                    for c in chunks:
                        score(c)
            """,
            "mmlspark_tpu/runner.py": BEAT_LOOPS_CLEAN})
        got = hits(active, "hot-path-host-sync",
                   "mmlspark_tpu/io/streaming.py")
        # the bare-import form ('from numpy import asarray') flags too —
        # the coverage the pre-graftlint guard had
        assert [f.line for f in got] == [7, 8, 9], active
        assert [f.line for f in suppressed] == [10]

    def test_nested_loop_reports_once(self, tmp_path):
        active, _sup = run_rule(tmp_path, "hot-path-host-sync", {
            "mmlspark_tpu/io/streaming.py": """\
                import numpy as np

                def stream_apply(chunks, fn):
                    for c in chunks:
                        for row in c:
                            np.asarray(row)
            """,
            "mmlspark_tpu/runner.py": BEAT_LOOPS_CLEAN})
        got = hits(active, "hot-path-host-sync",
                   "mmlspark_tpu/io/streaming.py")
        # both the inner and outer loop bodies contain the call; one
        # defect must be one finding
        assert [f.line for f in got] == [6], active

    def test_nested_function_loop_reports_once(self, tmp_path):
        active, _sup = run_rule(tmp_path, "hot-path-host-sync", {
            "mmlspark_tpu/io/streaming.py": STREAMING_CLEAN,
            "mmlspark_tpu/runner.py": BEAT_LOOPS_CLEAN,
            "mmlspark_tpu/train_loop.py": """\
                import numpy as np

                def outer(hb, steps, step):
                    def inner():
                        for it in steps:
                            hb.beat()
                            np.asarray(step(it))
                    return inner
            """})
        got = hits(active, "hot-path-host-sync",
                   "mmlspark_tpu/train_loop.py")
        # the loop belongs to inner() only — the module walk visiting
        # outer() must not scan it a second time (which also double-
        # counted the lint-rot hot-loop anchor)
        assert [(f.line, f.message.count("inner()")) for f in got] \
            == [(7, 1)], active

    def test_beat_registered_loop(self, tmp_path):
        active, _sup = run_rule(tmp_path, "hot-path-host-sync", {
            "mmlspark_tpu/io/streaming.py": STREAMING_CLEAN,
            "mmlspark_tpu/runner.py": BEAT_LOOPS_CLEAN,
            "mmlspark_tpu/train_loop.py": """\
                import numpy as np

                def round_loop(hb, steps, step):
                    for it in steps:
                        hb.beat()
                        out = step(it)
                        host = np.asarray(out)
                    return host

                def plain_loop_is_not_hot(steps, step):
                    for it in steps:
                        x = float(step(it))
                    return x
            """})
        got = hits(active, "hot-path-host-sync",
                   "mmlspark_tpu/train_loop.py")
        assert [f.line for f in got] == [7], active
        assert "watchdog-registered" in got[0].message

    def test_jit_functions(self, tmp_path):
        active, _sup = run_rule(tmp_path, "hot-path-host-sync", {
            "mmlspark_tpu/io/streaming.py": STREAMING_CLEAN,
            "mmlspark_tpu/runner.py": BEAT_LOOPS_CLEAN,
            "mmlspark_tpu/kernels.py": """\
                import jax
                import numpy as np

                @jax.jit
                def traced(x):
                    return x.item()

                def run(x):
                    return np.asarray(x)

                step = jax.jit(run)

                def not_compiled(x):
                    return float(np.asarray(x))
            """})
        got = hits(active, "hot-path-host-sync", "mmlspark_tpu/kernels.py")
        assert [f.line for f in got] == [6, 9], active
        assert all("jit-compiled" in f.message for f in got)


# --------------------------------------------------------------------------
# trees-as-arguments
# --------------------------------------------------------------------------

_BOOSTER_PREDICT = """\
    import numpy as np
    import jax.numpy as jnp

    class Booster:
        def predict(self, X):
            return self._predict_device(X)

        def predict_raw(self, X):
            return self._predict_device(X)

        def _predict_device(self, X):
            return self._device_forest_args()

        def _device_forest_args(self):
            packed = np.asarray(self.trees)        # host staging: legal
            return {}
"""


def test_trees_as_arguments(tmp_path):
    bad = _BOOSTER_PREDICT.replace(
        "        return {}",
        "        return jnp.asarray(self.trees)")
    active, _sup = run_rule(tmp_path, "trees-as-arguments", {
        "mmlspark_tpu/models/gbdt/booster.py": bad})
    got = hits(active, "trees-as-arguments")
    assert [f.line for f in got] == [16], active
    assert "bakes the forest" in got[0].message
    # the all-legal variant is clean
    active, _sup = run_rule(tmp_path, "trees-as-arguments", {
        "mmlspark_tpu/models/gbdt/booster.py": _BOOSTER_PREDICT})
    assert not active


# --------------------------------------------------------------------------
# resolve-before-cache-key
# --------------------------------------------------------------------------

_BOOSTER_PIN_OK = """\
    def resolve_predict_dtype(d):
        return d or "f32"

    def resolve_bucket_ladder():
        return ()

    def predict_plan(self, n, predict_dtype=None):
        ladder = resolve_bucket_ladder()
        predict_dtype = resolve_predict_dtype(predict_dtype)
        key = (n, predict_dtype)
        return key, ladder
"""


class TestResolveBeforeCacheKey:
    def test_general_env_read_after_key(self, tmp_path):
        active, suppressed = run_rule(
            tmp_path, "resolve-before-cache-key", {
                "mmlspark_tpu/models/gbdt/booster.py": _BOOSTER_PIN_OK,
                "mmlspark_tpu/engine.py": """\
                    import os

                    _PROGRAM_CACHE = {}

                    def build(n):
                        cache_key = ("p", n)
                        prog = _PROGRAM_CACHE.get(cache_key)
                        flavor = os.environ.get("X")
                        mode = resolve_mode(n)
                        ok = os.environ.get("Y")  # graftlint: disable=resolve-before-cache-key (test)
                        return prog, flavor, mode

                    def clean(n):
                        mode = resolve_mode(n)
                        cache_key = ("p", n, mode)
                        return _PROGRAM_CACHE.get(cache_key)
                """})
        got = hits(active, "resolve-before-cache-key",
                   "mmlspark_tpu/engine.py")
        assert [f.line for f in got] == [8, 9], active
        assert "os.environ" in got[0].message
        assert "resolve_mode" in got[1].message
        assert [f.line for f in suppressed] == [10]

    def test_predict_plan_pin_inversion(self, tmp_path):
        inverted = _BOOSTER_PIN_OK.replace(
            "        predict_dtype = resolve_predict_dtype(predict_dtype)\n"
            "        key = (n, predict_dtype)",
            "        key = (n, predict_dtype)\n"
            "        predict_dtype = resolve_predict_dtype(predict_dtype)")
        assert inverted != _BOOSTER_PIN_OK
        active, _sup = run_rule(tmp_path, "resolve-before-cache-key", {
            "mmlspark_tpu/models/gbdt/booster.py": inverted})
        got = hits(active, "resolve-before-cache-key",
                   "mmlspark_tpu/models/gbdt/booster.py")
        assert any("predict_plan's key assembly" in f.message
                   for f in got), active

    def test_predict_plan_pin_missing_resolver(self, tmp_path):
        unresolved = _BOOSTER_PIN_OK.replace(
            "        predict_dtype = resolve_predict_dtype(predict_dtype)\n",
            "")
        assert unresolved != _BOOSTER_PIN_OK
        active, _sup = run_rule(tmp_path, "resolve-before-cache-key", {
            "mmlspark_tpu/models/gbdt/booster.py": unresolved})
        got = hits(active, "resolve-before-cache-key",
                   "mmlspark_tpu/models/gbdt/booster.py")
        assert any("resolve_predict_dtype call missing" in f.message
                   for f in got), active

    def test_tuning_ladder_pin_inversion(self, tmp_path):
        inverted = _BOOSTER_PIN_OK.replace(
            "        ladder = resolve_bucket_ladder()\n"
            "        predict_dtype = resolve_predict_dtype(predict_dtype)\n"
            "        key = (n, predict_dtype)",
            "        predict_dtype = resolve_predict_dtype(predict_dtype)\n"
            "        key = (n, predict_dtype)\n"
            "        ladder = resolve_bucket_ladder()")
        assert inverted != _BOOSTER_PIN_OK
        active, _sup = run_rule(tmp_path, "resolve-before-cache-key", {
            "mmlspark_tpu/models/gbdt/booster.py": inverted})
        got = hits(active, "resolve-before-cache-key",
                   "mmlspark_tpu/models/gbdt/booster.py")
        assert any("tuning.resolve_bucket_ladder" in f.message
                   and "predict_plan's key assembly" in f.message
                   for f in got), active

    def test_tuning_ladder_pin_missing_resolver(self, tmp_path):
        unresolved = _BOOSTER_PIN_OK.replace(
            "        ladder = resolve_bucket_ladder()\n", "")
        unresolved = unresolved.replace("        return key, ladder",
                                        "        return key")
        assert unresolved != _BOOSTER_PIN_OK
        active, _sup = run_rule(tmp_path, "resolve-before-cache-key", {
            "mmlspark_tpu/models/gbdt/booster.py": unresolved})
        got = hits(active, "resolve-before-cache-key",
                   "mmlspark_tpu/models/gbdt/booster.py")
        assert any("resolve_bucket_ladder call missing" in f.message
                   for f in got), active


# --------------------------------------------------------------------------
# quantize-funnel
# --------------------------------------------------------------------------

_QUANTIZE_FUNNEL_OK = """\
    import numpy as np

    def resolve_predict_dtype(d):
        return d or "f32"

    def quantize_features(X, ub):
        return np.searchsorted(ub[0], X[:, 0], side="left")

    def quantize_leaves(lv):
        scale = np.abs(lv).max() / 127.0
        return np.clip(np.rint(lv / scale), -127, 127), scale
"""


class TestQuantizeFunnel:
    def test_stray_quantization_sites(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "quantize-funnel", {
            "mmlspark_tpu/models/gbdt/quantize.py": _QUANTIZE_FUNNEL_OK,
            "mmlspark_tpu/io/aserve/slots.py": """\
                import numpy as np

                def admit(row, ub, lv):
                    q = np.searchsorted(ub[0], row, side="left")
                    scale = np.abs(lv).max() / 127.0
                    qq = np.clip(np.rint(lv / scale), -127, 127)
                    r = np.searchsorted(ub[0], row, side="left")  # graftlint: disable=quantize-funnel (test)
                    return q, qq, r
            """})
        got = hits(active, "quantize-funnel",
                   "mmlspark_tpu/io/aserve/slots.py")
        assert [f.line for f in got] == [4, 5, 6], active
        assert "searchsorted" in got[0].message
        assert "scale" in got[1].message
        assert [f.line for f in suppressed] == [7]

    def test_non_grid_uses_and_training_funnel_clean(self, tmp_path):
        active, _sup = run_rule(tmp_path, "quantize-funnel", {
            "mmlspark_tpu/models/gbdt/quantize.py": _QUANTIZE_FUNNEL_OK,
            # shard-offset lookup (side="right") and the no-side weighted
            # median are NOT bin-grid quantization
            "mmlspark_tpu/models/gbdt/ingest.py": """\
                import numpy as np

                def shard_of(offsets, idx):
                    return np.searchsorted(offsets, idx, side="right") - 1
            """,
            "mmlspark_tpu/models/gbdt/objectives.py": """\
                import numpy as np

                def weighted_median(ys, cw, target):
                    return ys[np.searchsorted(cw, target)]
            """,
            # growth.py owns TRAINING gradient quantization — allowlisted
            "mmlspark_tpu/models/gbdt/growth.py": """\
                def quantized_grad(g, q_max):
                    return g / 127.0
            """})
        assert not hits(active, "quantize-funnel"), active

    def test_rots_when_funnel_vanishes(self, tmp_path):
        active, _sup = run_rule(tmp_path, "quantize-funnel", {
            "mmlspark_tpu/models/gbdt/quantize.py": """\
                def resolve_predict_dtype(d):
                    return d
            """})
        got = hits(active, "quantize-funnel", "<graftlint>")
        assert len(got) == 1 and "lint-rot" in got[0].message


# --------------------------------------------------------------------------
# resource-leak
# --------------------------------------------------------------------------

def test_resource_leak(tmp_path):
    active, suppressed = run_rule(tmp_path, "resource-leak", {
        "mmlspark_tpu/loops.py": """\
            def ok_with(_watchdog):
                with _watchdog.register("a") as hb:
                    hb.beat()

            def ok_conditional_finally(_watchdog, live):
                hb = _watchdog.register("b") if live else _watchdog.NOOP
                try:
                    hb.beat()
                finally:
                    hb.close()

            def leaky(_watchdog):
                hb = _watchdog.register("c")
                hb.beat()
                hb.close()

            def spans_ok(_spans):
                with _spans.span("one"):
                    pass
                with _spans.span("two"):
                    pass
                with _spans.span("three"):
                    pass
                with _spans.span("four"):
                    pass

            def span_leak(_spans):
                s = _spans.span("five")
                return s

            def span_suppressed(_spans):
                s = _spans.span("six")  # graftlint: disable=resource-leak (test)
                return s
        """})
    got = hits(active, "resource-leak")
    assert [f.line for f in got] == [13, 28], active
    assert "ghost" in got[0].message
    assert [f.line for f in suppressed] == [32]


# --------------------------------------------------------------------------
# lock-discipline
# --------------------------------------------------------------------------

_SIGNAL_RLOCK_OK = """\
    import signal
    import threading

    _ring = threading.RLock()

    def _dump():
        with _ring:
            pass

    def _on_sig(signum, frame):
        _dump()

    def install():
        signal.signal(signal.SIGUSR2, _on_sig)
"""


class TestLockDiscipline:
    def test_unguarded_shared_attr(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "lock-discipline", {
            "mmlspark_tpu/sig.py": _SIGNAL_RLOCK_OK,
            "mmlspark_tpu/box.py": """\
                import threading

                class Box:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._n = 0
                        self._name = "x"

                    def bump(self):
                        self._n += 1

                    def reset(self):
                        with self._lock:
                            self._n = 0

                    def rename(self, v):
                        self._name = v  # graftlint: disable=lock-discipline (test)

                    def rename2(self, v):
                        with self._lock:
                            self._name = v

                    def single_writer_is_fine(self):
                        self._only_here = 1
            """})
        got = hits(active, "lock-discipline", "mmlspark_tpu/box.py")
        assert [f.line for f in got] == [10], active
        assert "Box._n" in got[0].message
        assert [f.line for f in suppressed] == [17]

    def test_tuple_unpack_mutation_counts(self, tmp_path):
        active, _sup = run_rule(tmp_path, "lock-discipline", {
            "mmlspark_tpu/sig.py": _SIGNAL_RLOCK_OK,
            "mmlspark_tpu/box.py": """\
                import threading

                class Box:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._t = None

                    def start(self):
                        with self._lock:
                            self._t = object()

                    def stop(self):
                        self._t, old = None, self._t
                        return old
            """})
        got = hits(active, "lock-discipline", "mmlspark_tpu/box.py")
        # a tuple-unpacking write (self._t, x = ...) is a mutation like
        # any other — it must count toward the >=2-methods rule AND flag
        # when outside the lock
        assert [f.line for f in got] == [13], active
        assert "Box._t" in got[0].message

    def test_signal_handler_needs_rlock(self, tmp_path):
        bad = _SIGNAL_RLOCK_OK.replace("threading.RLock()",
                                       "threading.Lock()")
        active, _sup = run_rule(tmp_path, "lock-discipline", {
            "mmlspark_tpu/sig.py": bad,
            "mmlspark_tpu/box.py": """\
                import threading

                class Box:
                    def __init__(self):
                        self._lock = threading.Lock()
            """})
        got = hits(active, "lock-discipline", "mmlspark_tpu/sig.py")
        assert [f.line for f in got] == [7], active
        assert "RLock" in got[0].message
        # a non-stdlib .signal() (event emitter, scheduler) must NOT
        # mark its callback as signal-reachable
        active, _sup = run_rule(tmp_path, "lock-discipline", {
            "mmlspark_tpu/sig.py": _SIGNAL_RLOCK_OK,
            "mmlspark_tpu/box.py": """\
                import threading

                class Box:
                    def __init__(self):
                        self._lock = threading.Lock()
            """,
            "mmlspark_tpu/emitter.py": """\
                import threading

                _plain = threading.Lock()

                def worker():
                    with _plain:
                        pass

                def wire(bus):
                    bus.signal("done", worker)
            """})
        assert not any(f.path == "<graftlint>" for f in active), active
        assert not hits(active, "lock-discipline", "mmlspark_tpu/emitter.py")
        # ...and the RLock original is clean
        active, _sup = run_rule(tmp_path, "lock-discipline", {
            "mmlspark_tpu/sig.py": _SIGNAL_RLOCK_OK,
            "mmlspark_tpu/box.py": """\
                import threading

                class Box:
                    def __init__(self):
                        self._lock = threading.Lock()
            """})
        assert not hits(active, "lock-discipline", "mmlspark_tpu/sig.py")


# --------------------------------------------------------------------------
# env-var-registry
# --------------------------------------------------------------------------

_SEED_REGISTRY = """\
    from dataclasses import dataclass

    @dataclass(frozen=True)
    class EnvVar:
        name: str
        default: str
        doc: str
        section: str = "observability"
        where: str = "python"

    REGISTRY = (
""" + "\n".join(
    f'        EnvVar(name="MMLSPARK_TPU_V{i}", default="", doc="v{i}"),'
    for i in range(9)) + """
        EnvVar(name="MMLSPARK_TPU_UNUSED", default="", doc="stale"),
        EnvVar(name="MMLSPARK_TPU_NODOC", default="", doc=""),
        EnvVar(name="MMLSPARK_TPU_NATIVE_ONLY", default="", doc="cpp",
               where="native"),
    )
"""


def test_env_var_registry(tmp_path):
    active, suppressed = run_rule(tmp_path, "env-var-registry", {
        "mmlspark_tpu/observability/env_registry.py": _SEED_REGISTRY,
        "mmlspark_tpu/reader.py": """\
            import os

            _KNOWN = ["MMLSPARK_TPU_V0", "MMLSPARK_TPU_V1",
                      "MMLSPARK_TPU_V2", "MMLSPARK_TPU_V3",
                      "MMLSPARK_TPU_V4", "MMLSPARK_TPU_V5",
                      "MMLSPARK_TPU_V6", "MMLSPARK_TPU_V7",
                      "MMLSPARK_TPU_V8", "MMLSPARK_TPU_NODOC"]

            def read():
                vals = [os.environ.get(n) for n in _KNOWN]
                rogue = os.environ.get("MMLSPARK_TPU_ROGUE")
                ok = os.environ.get("MMLSPARK_TPU_ALSO_ROGUE")  # graftlint: disable=env-var-registry (test)
                return vals, rogue, ok
        """})
    reader_hits = hits(active, "env-var-registry", "mmlspark_tpu/reader.py")
    assert [f.line for f in reader_hits] == [11], active
    assert "MMLSPARK_TPU_ROGUE" in reader_hits[0].message
    reg_hits = hits(active, "env-var-registry",
                    "mmlspark_tpu/observability/env_registry.py")
    msgs = " | ".join(f.message for f in reg_hits)
    assert "MMLSPARK_TPU_UNUSED" in msgs       # declared but never read
    assert "MMLSPARK_TPU_NODOC" in msgs        # declared without a doc
    assert "MMLSPARK_TPU_NATIVE_ONLY" not in msgs   # where="native": exempt
    assert [f.line for f in suppressed] == [12]
    assert "MMLSPARK_TPU_V0" not in msgs      # declared AND read: clean


# --------------------------------------------------------------------------
# failpoint-site-grammar
# --------------------------------------------------------------------------

_SEED_FAILPOINTS = """\
    SITES = {
        "serving.handle": "worker HTTP handler",
        "dead.site": "registered but wired nowhere",
    }

    def fault_point(site, **ctx):
        return None
"""


def test_failpoint_site_grammar(tmp_path):
    active, suppressed = run_rule(tmp_path, "failpoint-site-grammar", {
        "mmlspark_tpu/robustness/failpoints.py": _SEED_FAILPOINTS,
        "mmlspark_tpu/io/serving.py": """\
            from ..robustness.failpoints import fault_point as _failpoint

            def handle(which):
                _failpoint("serving.handle")
                _failpoint("serving.hanlde")
                _failpoint("Serving.Handle")
                _failpoint(which)
                _failpoint("nope.site")  # graftlint: disable=failpoint-site-grammar (test)
        """})
    got = hits(active, "failpoint-site-grammar",
               "mmlspark_tpu/io/serving.py")
    # the typo'd site, the grammar violation, and the non-literal arg —
    # the correctly wired literal on line 4 is clean
    assert [f.line for f in got] == [5, 6, 7], active
    assert "serving.hanlde" in got[0].message
    assert "grammar" in got[1].message
    assert "non-literal" in got[2].message
    assert [f.line for f in suppressed] == [8]
    # the registered-but-unwired site flags at its SITES entry
    reg = hits(active, "failpoint-site-grammar",
               "mmlspark_tpu/robustness/failpoints.py")
    assert len(reg) == 1 and "dead.site" in reg[0].message, active


def test_failpoint_site_grammar_rot(tmp_path):
    """failpoints.py losing its literal SITES dict is lint-rot, not a
    silent pass."""
    active, _sup = run_rule(tmp_path, "failpoint-site-grammar", {
        "mmlspark_tpu/robustness/failpoints.py":
            "def fault_point(site, **ctx):\n    return None\n",
        "mmlspark_tpu/io/serving.py": """\
            from ..robustness.failpoints import fault_point as _failpoint

            def handle():
                _failpoint("anything.here")
        """})
    rot = [f for f in active if f.rule == "failpoint-site-grammar"
           and "lint-rot" in f.message]
    assert rot, active


# --------------------------------------------------------------------------
# debug-route-registry
# --------------------------------------------------------------------------

#: the anchor the rule parses: string-constant indirection plus inline
#: literals, exactly serving.py's table shape
_DEBUG_ROUTES_OK = """\
    METRICS_PATH = "/debug/metrics"
    SLO_PATH = "/debug/slo"

    DEBUG_ROUTES = (
        ("metrics", METRICS_PATH),
        ("slo", SLO_PATH),
        ("flight", "/debug/flight"),
    )
"""


class TestDebugRouteRegistry:
    def test_undeclared_route_literal_flagged(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "debug-route-registry", {
            "mmlspark_tpu/io/serving.py": _DEBUG_ROUTES_OK,
            "mmlspark_tpu/io/aserve/server.py": """\
                def handle(path):
                    if path == "/debug/flight":      # declared: fine
                        return b"{}"
                    if path == "/debug/slo/":        # trailing /: declared
                        return b"{}"
                    if path == "/debug/rogue":       # not in the table
                        return b"{}"
                    if path == "/debug/rogue2":  # graftlint: disable=debug-route-registry (test)
                        return b"{}"
                    return None
            """})
        got = hits(active, "debug-route-registry",
                   "mmlspark_tpu/io/aserve/server.py")
        assert [f.line for f in got] == [6], active
        assert "DEBUG_ROUTES" in got[0].message
        assert [f.line for f in suppressed] == [8]

    def test_outside_io_and_docstrings_clean(self, tmp_path):
        active, _sup = run_rule(tmp_path, "debug-route-registry", {
            "mmlspark_tpu/io/serving.py": _DEBUG_ROUTES_OK,
            # tools/monitoring prose may name any route; only io/ is the
            # serving plane the funnel contract binds
            "mmlspark_tpu/observability/federation.py": """\
                SCRAPE = "/debug/undeclared_elsewhere"
            """,
            "mmlspark_tpu/io/distributed_serving.py": """\
                def scrape(worker):
                    return worker + "/debug/metrics"
            """})
        assert not hits(active, "debug-route-registry"), active

    def test_rots_when_table_vanishes(self, tmp_path):
        active, _sup = run_rule(tmp_path, "debug-route-registry", {
            "mmlspark_tpu/io/serving.py": """\
                ROUTES = {"metrics": "/debug/metrics"}
            """})
        got = hits(active, "debug-route-registry", "<graftlint>")
        assert len(got) == 1 and "lint-rot" in got[0].message, active

    def test_real_table_declares_timeline_and_trace(self):
        # the fleet black-box routes ride the same funnel: the live table
        # must declare them, or the corpus rule above couldn't vouch for
        # the real handlers
        from tools.graftlint.checks.debugroutes import _declared_paths
        declared = _declared_paths(core.Repo(ROOT))
        assert {"/debug/flight", "/debug/timeline",
                "/debug/trace"} <= declared


# --------------------------------------------------------------------------
# postmortem-scrape-only
# --------------------------------------------------------------------------

class TestPostmortemScrapeOnly:
    def test_stdlib_only_collector_clean(self, tmp_path):
        active, _sup = run_rule(tmp_path, "postmortem-scrape-only", {
            "mmlspark_tpu/__init__.py": "",
            "tools/postmortem.py": """\
                import json
                import urllib.request

                def fetch(addr, path):
                    with urllib.request.urlopen(
                            f"http://{addr}{path}") as r:
                        return json.load(r)
            """})
        assert not hits(active, "postmortem-scrape-only"), active

    def test_framework_imports_flagged(self, tmp_path):
        active, _sup = run_rule(tmp_path, "postmortem-scrape-only", {
            "mmlspark_tpu/__init__.py": "",
            "tools/postmortem.py": """\
                import json
                import mmlspark_tpu.observability.flight as _flight
                from mmlspark_tpu.io.serving import debug_body

                def collect():
                    return debug_body("flight", "pm")
            """})
        got = hits(active, "postmortem-scrape-only", "tools/postmortem.py")
        assert [f.line for f in got] == [2, 3], active
        assert "scrape-read-only" in got[0].message

    def test_rots_when_tool_vanishes(self, tmp_path):
        active, _sup = run_rule(tmp_path, "postmortem-scrape-only", {
            "mmlspark_tpu/__init__.py": ""})
        got = hits(active, "postmortem-scrape-only", "<graftlint>")
        assert len(got) == 1 and "lint-rot" in got[0].message, active


# --------------------------------------------------------------------------
# infrastructure
# --------------------------------------------------------------------------

class TestInfrastructure:
    def test_file_level_suppression(self, tmp_path):
        active, suppressed = run_rule(tmp_path, "raw-output-funnel", {
            "mmlspark_tpu/observability/logging.py": OBS_LOGGING,
            "mmlspark_tpu/demo.py": """\
                # graftlint: disable-file=raw-output-funnel
                def f():
                    print("a")
                    print("b")
            """})
        assert not active
        assert [f.line for f in suppressed] == [3, 4]

    def test_unknown_rule_raises(self, tmp_path):
        (tmp_path / "mmlspark_tpu").mkdir()
        repo = core.Repo(str(tmp_path))
        with pytest.raises(ValueError, match="no-such-rule"):
            core.run(repo, rules=["no-such-rule"])

    def test_rot_becomes_finding(self, tmp_path):
        # trees-as-arguments without booster.py: the guard's anchor is
        # gone, which must FAIL the run, not silently pass
        (tmp_path / "mmlspark_tpu").mkdir()
        repo = core.Repo(str(tmp_path))
        active, _sup = core.run(repo, rules=["trees-as-arguments"])
        assert len(active) == 1
        assert active[0].rule == "trees-as-arguments"
        assert "lint-rot" in active[0].message

    def test_rot_keeps_earlier_findings(self, tmp_path):
        # checkers yield real violations before raising their rot check —
        # the rot finding must be ADDED, not mask what was already found
        active, _sup = run_rule(tmp_path, "lock-discipline", {
            "mmlspark_tpu/box.py": """\
                import threading

                class Box:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._n = 0

                    def bump(self):
                        self._n += 1

                    def reset(self):
                        with self._lock:
                            self._n = 0
            """})
        # no signal.signal anywhere -> the rule's handler anchor rots,
        # but the unguarded Box._n write must still be reported
        rules = [f.rule for f in active]
        assert rules == ["lock-discipline", "lock-discipline"], active
        assert any("Box._n" in f.message for f in active), active
        assert any("lint-rot" in f.message for f in active), active

    def test_duplicate_rule_runs_once(self, tmp_path):
        active, _sup = run_rule(tmp_path, "raw-output-funnel", {
            "mmlspark_tpu/observability/logging.py": OBS_LOGGING,
            "mmlspark_tpu/worker.py": "def f():\n    print('x')\n"})
        repo = core.Repo(str(tmp_path))
        twice, _sup = core.run(repo, rules=["raw-output-funnel",
                                            "raw-output-funnel"])
        assert len(twice) == len(active) == 1, twice

    def test_env_registry_validates_entries(self):
        from mmlspark_tpu.observability.env_registry import EnvVar
        with pytest.raises(ValueError, match="unknown section"):
            EnvVar(name="MMLSPARK_TPU_X", default="0", doc="d",
                   section="perfomance")
        with pytest.raises(ValueError, match="unknown where"):
            EnvVar(name="MMLSPARK_TPU_X", default="0", doc="d",
                   where="pyhton")
        with pytest.raises(ValueError, match="MMLSPARK_TPU_"):
            EnvVar(name="GRAFT_BENCH_X", default="0", doc="d")

    def test_parse_error_is_a_finding(self, tmp_path):
        p = tmp_path / "mmlspark_tpu" / "broken.py"
        p.parent.mkdir(parents=True)
        p.write_text("def f(:\n")
        repo = core.Repo(str(tmp_path))
        active, _sup = core.run(repo, rules=[])
        assert [f.rule for f in active] == ["parse-error"]

    def test_cli_list_rules_and_json(self, tmp_path):
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "--list-rules"],
            capture_output=True, text=True, timeout=120, cwd=ROOT)
        assert r.returncode == 0, r.stderr
        for rule in ("raw-output-funnel", "hot-path-host-sync",
                     "lock-discipline", "env-var-registry"):
            assert rule in r.stdout
        # seeded bad repo: non-zero exit + machine-readable findings
        pkg = tmp_path / "mmlspark_tpu"
        (pkg / "observability").mkdir(parents=True)
        (pkg / "observability" / "logging.py").write_text(
            textwrap.dedent(OBS_LOGGING))
        (pkg / "bad.py").write_text("def f():\n    print('x')\n")
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "--json",
             "--rule", "raw-output-funnel", str(tmp_path)],
            capture_output=True, text=True, timeout=120, cwd=ROOT)
        assert r.returncode == 1, r.stdout + r.stderr
        data = json.loads(r.stdout)
        assert data["findings"][0]["rule"] == "raw-output-funnel"
        assert data["findings"][0]["path"] == "mmlspark_tpu/bad.py"
        assert data["findings"][0]["line"] == 2

    def test_cli_clean_on_this_repo(self):
        """The acceptance criterion: the shipped tree lints clean."""
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "--json"],
            capture_output=True, text=True, timeout=300, cwd=ROOT)
        assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
        data = json.loads(r.stdout)
        assert data["findings"] == []
        assert len(data["rules"]) >= 14

    def test_env_docs_generator_in_sync(self):
        """docs tables are generated from the registry; --check gates
        drift (the satellite's one-source-of-truth contract)."""
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools",
                                          "gen_env_docs.py"), "--check"],
            capture_output=True, text=True, timeout=120, cwd=ROOT)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_env_registry_render(self):
        from mmlspark_tpu.observability import env_registry
        md = env_registry.render_markdown()
        for v in env_registry.REGISTRY:
            assert v.name in md
        obs = env_registry.render_markdown("observability")
        assert "MMLSPARK_TPU_LOG_LEVEL" in obs
        assert "MMLSPARK_TPU_HIST_ENGINE" not in obs
        assert env_registry.get("MMLSPARK_TPU_LOG_RATE").default == "200"


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
