"""The serving engine's stage spans (``serving.spans``): each stage of the
host path is counted once per call into ``stats()["stages"]``, nests
under the stage that opened it, survives concurrent pool threads without
losing counts, resets with the other stats, and reaches the profiler as an
``engine.*`` host event carrying its ids."""
import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import executor as exe, gcn  # noqa: E402
from repro.graphs import synth  # noqa: E402
from repro.serving.gcn_engine import GCNServingEngine  # noqa: E402
from repro.serving.spans import Spans  # noqa: E402
from repro.tuning import registry  # noqa: E402

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
N_NODES = 200
N_FEATS = 12
SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
FAST_KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)


@pytest.fixture(autouse=True)
def _fresh_caches():
    registry.clear_caches()
    yield
    registry.clear_caches()


def _engine(root, **kw):
    a = synth.power_law_adjacency(N_NODES, 0.03, 0.9, seed=1)
    params = gcn.init_params(gcn.GCNConfig(N_FEATS, 8, 3), jax.random.PRNGKey(1))
    eng = GCNServingEngine(store_root=root, autotune_kwargs=FAST_KW, **kw)
    eng.add_graph("g", a, params)
    return eng


def _requests(k):
    rng = np.random.default_rng(2)
    return [rng.random((N_NODES, N_FEATS)).astype(np.float32) for _ in range(k)]


def _serve(eng, xs):
    """Submit every request, then poll until all are answered."""
    got = 0
    for x in xs:
        assert eng.submit("g", x, deadline_s=0.0).accepted
    while got < len(xs):
        out = eng.poll().get("g")
        if out is not None:
            got += int(jax.block_until_ready(out).shape[0])


def test_submit_and_poll_fill_the_stages(tmp_path):
    eng = _engine(tmp_path, max_batch=4)
    eng.reset_stats()
    xs = _requests(10)  # two auto-flushed batches of 4, one polled of 2
    _serve(eng, xs)
    st = eng.stats()
    stages = st["stages"]
    assert stages["submit"]["n"] == stages["copy"]["n"] == st["submitted"] == 10
    assert stages["dispatch"]["n"] == stages["await"]["n"] == st["batches"] == 3
    assert stages["stack"]["n"] == 3
    assert stages["poll"]["n"] >= 1
    assert stages["drain"]["n"] == 1  # the poll joins 3 batches' logits
    assert st["h2d_bytes"] == sum(x.nbytes for x in xs)
    # a request already on the device crosses nothing
    eng.submit("g", jax.numpy.asarray(xs[0]))
    assert eng.stats()["h2d_bytes"] == sum(x.nbytes for x in xs)
    assert eng.stats()["stages"]["copy"]["n"] == 11


def test_self_time_excludes_nested_spans(tmp_path):
    eng = _engine(tmp_path, max_batch=4)
    eng.reset_stats()
    _serve(eng, _requests(10))
    stages = eng.stats()["stages"]
    for name, s in stages.items():
        assert 0.0 <= s["self_s"] <= s["s"], name
    # stack is dispatch's only child; copy, dispatch, await and drain are
    # the children of submit and poll (merge and stack are grandchildren)
    child = lambda name: stages[name]["s"] - stages[name]["self_s"]  # noqa: E731
    assert child("dispatch") == pytest.approx(stages["stack"]["s"], rel=1e-6)
    assert child("submit") + child("poll") == pytest.approx(
        sum(stages[k]["s"] for k in ("copy", "dispatch", "await", "drain")),
        rel=1e-6)
    assert child("submit") >= stages["copy"]["s"]


def test_reset_stats_zeroes_the_stages(tmp_path):
    eng = _engine(tmp_path, max_batch=2)
    _serve(eng, _requests(4))
    assert eng.stats()["stages"] and eng.stats()["h2d_bytes"] > 0
    eng.reset_stats()
    assert eng.stats()["stages"] == {} and eng.stats()["h2d_bytes"] == 0
    _serve(eng, _requests(2))
    assert eng.stats()["stages"]["copy"]["n"] == 2


def test_serve_batch_records_dispatch_and_await(tmp_path):
    eng = _engine(tmp_path)
    eng.reset_stats()
    eng.serve_batch("g", _requests(3))
    stages = eng.stats()["stages"]
    assert stages["dispatch"]["n"] == stages["await"]["n"] == 1
    assert stages["stack"]["n"] == 1 and "copy" not in stages


def test_spans_from_many_threads_lose_no_count():
    spans = Spans()
    threads, per = 16, 400
    start = threading.Barrier(threads)

    def work():
        start.wait(timeout=30)
        for _ in range(per):
            with spans.span("outer"):
                with spans.span("inner", n=1):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    snap = spans.snapshot()
    assert snap["outer"]["n"] == snap["inner"]["n"] == threads * per
    # each thread nests its own spans: outer's children are exactly inner
    assert snap["outer"]["s"] - snap["outer"]["self_s"] == pytest.approx(
        snap["inner"]["s"], rel=1e-6)
    assert snap["inner"]["self_s"] == pytest.approx(snap["inner"]["s"], rel=1e-9)


def test_a_span_closes_on_a_raise():
    spans = Spans()
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("inner"):
                raise ValueError
    with spans.span("after"):
        time.sleep(0.001)
    snap = spans.snapshot()
    assert snap["outer"]["n"] == snap["inner"]["n"] == snap["after"]["n"] == 1
    assert snap["after"]["self_s"] == snap["after"]["s"]  # the stack unwound


def _host_events(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((line.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name, dict(ev.stats)))
    return out


def test_profiler_sees_engine_spans_with_their_ids(tmp_path):
    eng = _engine(tmp_path / "store", max_batch=4)
    xs = _requests(4)
    _serve(eng, xs)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        _serve(eng, xs)
    files = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert files
    evs = _host_events(files[0])
    copies = [e for e in evs if e[3] == "engine.copy"]
    submits = [e for e in evs if e[3] == "engine.submit"]
    assert len(copies) == len(submits) == 4
    rids = sorted(e[4]["rid"] for e in copies)
    assert rids == list(range(rids[0], rids[0] + 4))
    assert all(e[4]["bytes"] == xs[0].nbytes for e in copies)
    for line, s, e, _, ids in copies:
        outer = [o for o in submits
                 if o[0] == line and o[1] <= s and e <= o[2]]
        assert len(outer) == 1 and outer[0][4]["rid"] == ids["rid"]
    (disp,) = [e for e in evs if e[3] == "engine.dispatch"]
    assert disp[4]["n"] == 4 and disp[4]["rid0"] == rids[0]
    assert disp[4]["graph"] == "g"


SCRIPT_REPLICAS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, tempfile
sys.path.insert(0, %r)
import numpy as np, jax
from repro.core import executor as exe, gcn
from repro.graphs import synth
from repro.serving.gcn_engine import GCNServingEngine
assert len(jax.devices()) == 4

SWEEP = [dict(nnz_per_step=64, rows_per_window=32, cols_per_block=None,
              window_nnz=None, routing=exe.GATHER)]
KW = dict(iters=1, warmup=1, sweep=SWEEP, bf16_report=False)
n = 200
a = synth.power_law_adjacency(n, 0.03, 0.9, seed=5)
params = gcn.init_params(gcn.GCNConfig(12, 8, 3), jax.random.PRNGKey(5))
x = np.random.default_rng(5).random((n, 12)).astype(np.float32)
reqs = [x * (1.0 - 0.02 * i) for i in range(8)]
eng = GCNServingEngine(store_root=tempfile.mkdtemp(), devices=4, max_replicas=4,
                       replicate_after_s=1e-6, autotune_kwargs=KW)
eng.add_graph("hot", a, params)
eng.serve_batch("hot", reqs[:2])  # prime the service EWMA
parts = []
orig = eng._dispatch_batch
def counted(gid, xs):
    out = orig(gid, xs)
    parts.append(len(out))
    return out
eng._dispatch_batch = counted
eng.reset_stats()
for _ in range(4):
    for r in reqs:
        eng.submit("hot", r, deadline_s=0.0)
    jax.block_until_ready(eng.poll()["hot"])
st = eng.stats()
stages = st["stages"]
assert len(st["replicas"]["hot"]) == 4, st["replicas"]
chunks = sum(p for p in parts if p > 1)
assert chunks >= 8, parts
assert stages["chunk"]["n"] == chunks, (stages["chunk"], parts)
assert abs(stages["chunk"]["self_s"] - stages["chunk"]["s"]) < 1e-6
assert stages["merge"]["n"] == sum(1 for p in parts if p > 1)
assert stages["dispatch"]["n"] == stages["await"]["n"] == st["batches"] == 4
print("CHUNKS OK", parts)
""" % (SRC,)


@pytest.mark.distributed
def test_replica_chunks_record_spans_from_pool_threads():
    r = subprocess.run([sys.executable, "-c", SCRIPT_REPLICAS],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "CHUNKS OK" in r.stdout
