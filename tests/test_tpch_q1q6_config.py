"""The TPC-H lineitem Q1/Q6 deployment (chipbench configuration
``tpch_sf5_q1q6``, cell ``tpch_sf5_q1q6.power``) at test size on the
CPU backend: both queries through ``session.sql(...).collect()`` over
many small reader batches equal the plain reference on the pass that
decodes and uploads and on the pass the device scan cache replays; the
``scan.*`` counters say which of the two a pass was, and what a cache
budget too small for one scan, or for both, did; the cell resolves by
name and a whole rehearsal comes out ``correct`` while the float32
control does not; the two metric readers the cell adds return nothing
where there is nothing to read."""
import importlib
import os
import sys
from types import SimpleNamespace

import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.io import planner as io_planner
from spark_rapids_tpu.io.scan_cache import DeviceScanCache
from spark_rapids_tpu.obs import trace

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
CELL = "tpch_sf5_q1q6.power"
SCALE = 0.01                    # 60,000 rows
SEED = 2147483659
#: 3.3 N/F rows a batch on average, so some batch holds none
BATCH_ROWS = 512
CACHE_BYTES = "spark.rapids.tpu.io.deviceScanCache.bytes"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def bench():
    """``chipbench/``'s harness, reference and span reduction,
    importable."""
    sys.path.insert(0, CHIPBENCH)
    import reference
    import run as harness
    import span_reduce
    yield SimpleNamespace(harness=harness, reference=reference,
                          span_reduce=span_reduce)
    sys.path.remove(CHIPBENCH)
    for name in ("run", "span_reduce", "reference", "datagen",
                 "datagen.tpch"):
        sys.modules.pop(name, None)


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):
    """The cell, its table at test size and the reference's answers."""
    cell = bench.harness.load_cell(CELL)
    config = cell["config"]
    data_dir = str(tmp_path_factory.mktemp("tpch_q1q6"))
    rows = importlib.import_module(f"datagen.{config['schema']}").generate(
        data_dir, SCALE, SEED, sorted(config["tables"]))
    want, _ = bench.reference.answers(
        cell["config_name"], config["queries"], data_dir,
        config["precision"])
    return SimpleNamespace(cell=cell, config=config, data_dir=data_dir,
                           want=want, rows=rows["lineitem"])


@pytest.fixture(autouse=True)
def _fresh_scan_cache():
    DeviceScanCache.get().clear()
    yield
    DeviceScanCache.get().clear()


def _session(dep, tmp_path, **conf):
    settings = dict(dep.config["engine_conf"])
    settings["spark.rapids.tpu.sql.reader.batchSizeRows"] = BATCH_ROWS
    settings["spark.rapids.tpu.memory.spill.dir"] = str(tmp_path / "spill")
    settings.update(conf)
    s = TpuSession(TpuConf(settings))
    s.read.parquet(os.path.join(dep.data_dir, "lineitem.parquet")) \
        .create_or_replace_temp_view("lineitem")
    return s


def _scan_counts():
    total = {}
    for tbl in trace.coarse_counts().values():
        for name, n in tbl.items():
            if name.startswith("scan."):
                total[name] = total.get(name, 0) + n
    return total


def _collect(bench, dep, session, q):
    """One query through the harness's ``run_query``, compared with the
    reference under the configuration's limits -> its ``scan.*``
    counters."""
    trace.reset()
    rec = bench.harness.run_query(session, q, dep.cell["texts"][q])
    assert rec["error"] is None
    c = bench.reference.compare(rec["rows"], dep.want[q])
    limits = dep.config["limits"]
    assert c["wrong_cells"] <= limits["wrong_cells"]
    assert c["max_rel_gap"] <= limits["max_rel_gap"]
    return _scan_counts()


@pytest.fixture
def uploads(monkeypatch):
    """Every Arrow chunk a scan uploads: (rows, N/F rows or None)."""
    seen = []
    real = io_planner.from_arrow

    def spy(chunk):
        small = None
        if "l_returnflag" in chunk.column_names:
            flag = chunk.column("l_returnflag").to_pylist()
            status = chunk.column("l_linestatus").to_pylist()
            small = sum(1 for f, s in zip(flag, status)
                        if (f, s) == ("N", "F"))
        seen.append((chunk.num_rows, small))
        return real(chunk)
    monkeypatch.setattr(io_planner, "from_arrow", spy)
    return seen


# ---------------------------------------------------------------------------
# (a) the decode-and-upload pass and the cache-replay pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q", ["q1", "q6"])
def test_answers_on_the_decode_pass_and_the_replay_pass(
        bench, deployment, tmp_path, uploads, q):
    s = _session(deployment, tmp_path)
    first = _collect(bench, deployment, s, q)
    batches, rows = len(uploads), sum(n for n, _ in uploads)
    assert batches >= 8 and 0 < rows <= deployment.rows
    assert first == {"scan.batches.read": batches, "scan.rows": rows}
    if q == "q1":
        small = [k for _, k in uploads]
        assert 0 in small and sum(small) > 0    # a batch with no N/F row
        assert len(deployment.want[q]) == 4
    del uploads[:]
    second = _collect(bench, deployment, s, q)
    assert not uploads
    assert second == {"scan.batches.cached": batches, "scan.rows": rows}
    # a warm query's coarse spans do not grow with its batches: a window
    # of such queries cannot wrap the ring
    assert trace.get_tracer().ring_written() < trace.RING_SLOTS // 64


# ---------------------------------------------------------------------------
# (b) a cache budget under one scan, and under the two scans' sum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q", ["q1", "q6"])
def test_a_budget_under_one_scan_abandons_collection(
        bench, deployment, tmp_path, q):
    s = _session(deployment, tmp_path, **{CACHE_BYTES: 1024})
    passes = [_collect(bench, deployment, s, q) for _ in range(2)]
    assert passes[0] == passes[1]
    assert passes[0]["scan.cache.abandoned"] == 1
    assert passes[0]["scan.batches.read"] >= 8
    assert set(passes[0]) == {"scan.batches.read", "scan.rows",
                              "scan.cache.abandoned"}
    assert DeviceScanCache.get().nbytes == 0


@pytest.fixture(scope="module")
def scan_sizes(bench, deployment, tmp_path_factory):
    """Device bytes of Q1's and of Q6's cached scan."""
    cache = DeviceScanCache.get()
    cache.clear()
    s = _session(deployment, tmp_path_factory.mktemp("sizes"))
    _collect(bench, deployment, s, "q1")
    one = cache.nbytes
    _collect(bench, deployment, s, "q6")
    both = cache.nbytes
    cache.clear()
    assert 0 < one < both
    return one, both - one


def test_a_budget_under_both_scans_evicts_the_other_query(
        bench, deployment, tmp_path, scan_sizes):
    n1, n6 = scan_sizes
    s = _session(deployment, tmp_path, **{CACHE_BYTES: n1 + n6 - 1})
    got = [_collect(bench, deployment, s, q)
           for q in ("q1", "q6", "q1", "q6")]
    assert "scan.cache.evicted" not in got[0]
    for counts in got[1:]:
        assert counts["scan.cache.evicted"] == 1
    for counts in got:
        # each query finds the other's scan where its own was
        assert "scan.batches.cached" not in counts
        assert "scan.cache.abandoned" not in counts
        assert counts["scan.batches.read"] >= 8


def test_a_budget_of_both_scans_keeps_them_side_by_side(
        bench, deployment, tmp_path, scan_sizes):
    n1, n6 = scan_sizes
    s = _session(deployment, tmp_path, **{CACHE_BYTES: n1 + n6})
    first = [_collect(bench, deployment, s, q) for q in ("q1", "q6")]
    second = [_collect(bench, deployment, s, q) for q in ("q1", "q6")]
    for cold, warm in zip(first, second):
        assert set(cold) == {"scan.batches.read", "scan.rows"}
        assert warm == {"scan.batches.cached": cold["scan.batches.read"],
                        "scan.rows": cold["scan.rows"]}
    assert DeviceScanCache.get().nbytes == n1 + n6


# ---------------------------------------------------------------------------
# (c) the cell by name, a whole rehearsal, and the float32 control
# ---------------------------------------------------------------------------

def test_the_cell_resolves_by_name(bench, deployment):
    cell, config = deployment.cell, deployment.config
    assert cell["config_name"] == config["name"] == "tpch_sf5_q1q6"
    assert cell["chips"] == 1 and config["scale"] == 5.0
    assert config["tables"]["lineitem"]["rows"] == 30_000_000
    assert config["queries"] == ["q1", "q6"] == sorted(cell["texts"])
    assert len(config["source"]) <= 200 and "status" not in config
    assert sorted(config["reduced"]) == ["columns", "scale_factor",
                                         "tables"]
    assert config["engine_conf"] == {
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.test.enabled": True}
    mine = {"scan_cache_hit_pct", "device_ns_per_scanned_row"}
    assert mine <= {m["name"] for m in cell["per_layer"]}
    other = bench.harness.load_cell("tpcds_sf1_store.power")
    assert not mine & {m["name"] for m in other["per_layer"]}
    for name in mine:
        assert callable(bench.harness.metric_reader(name))


@pytest.mark.parametrize("traced", [False, True])
def test_a_rehearsal_of_the_cell_is_correct(
        bench, deployment, tmp_path, monkeypatch, traced):
    monkeypatch.setattr(bench.harness, "DATA_DIR", str(tmp_path))
    result = bench.harness.run_cell(deployment.cell, SEED, 0.5, traced,
                                    scale=SCALE, device=dict(DEVICE))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert list(result)[-1] == "compared"
    if traced:
        # no chip: nothing of the device trace, the spans or the counters
        assert not {"scan_cache_hit_pct", "device_ns_per_scanned_row"} \
            & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"queries_per_hour",
                                          "query_p95_s", "setup_s"}


def test_the_float32_control_is_not_correct(bench, deployment):
    config = deployment.config
    low, _ = bench.reference.answers(
        deployment.cell["config_name"], config["queries"],
        deployment.data_dir, config["control_precision"])
    run = {"queries": [{"name": q, "rows": low[q]}
                       for q in deployment.want]}
    compared = bench.harness.verdict(run, deployment.want,
                                     config["limits"])
    assert compared["wrong_cells"]["value"] == 0
    assert compared["max_rel_gap"]["value"] > \
        3 * compared["max_rel_gap"]["limit"]
    assert not all(r["verified"] for r in run["queries"])


# ---------------------------------------------------------------------------
# (d) the two metric readers on hand-made runs
# ---------------------------------------------------------------------------

#: two passes of q1, q6; the traced pass is the first
RUN = {"queries": [{"done": 0.2, "seconds": 0.1},
                   {"done": 0.4, "seconds": 0.1},
                   {"done": 0.6, "seconds": 0.1},
                   {"done": 0.8, "seconds": 0.1}],
       "peaks": {"hbm_gbps": 1},
       "trace": {"queries": ["q1", "q6"], "busy_s": 3.0, "window_s": 4.0}}


def _window(counts):
    """Query numbers 7-10, one ``srt.query`` span inside each record's
    interval."""
    spans = [{"id": i, "parent": 0, "name": "srt.query", "query": 7 + i,
              "t0_ns": int((0.15 + 0.2 * i) * 1e9), "dur_ns": 1000}
             for i in range(4)]
    return {"spans": spans, "self_ns": {}, "n_queries": 4,
            "counts": counts}


def _scan(cached=0, read=0, rows=0):
    tbl = {"eager.column_gather": 40}
    for name, n in (("scan.batches.cached", cached),
                    ("scan.batches.read", read), ("scan.rows", rows)):
        if n:
            tbl[name] = n
    return tbl


@pytest.mark.parametrize("metric,counts,want", [
    ("scan_cache_hit_pct",
     {7: _scan(cached=30), 8: _scan(cached=5), 9: _scan(cached=30),
      10: _scan(cached=5)}, 100.0),
    # the second pass found the first query's scan evicted
    ("scan_cache_hit_pct",
     {7: _scan(cached=30), 8: _scan(cached=5), 9: _scan(read=30),
      10: _scan(cached=5)}, 100.0 * 40 / 70),
    ("scan_cache_hit_pct", {7: _scan(read=30), 8: _scan(read=5)}, 0.0),
    # an engine without the counters (the parent)
    ("scan_cache_hit_pct", {7: _scan(), 8: _scan()}, None),
    # the traced pass's rows alone: queries 7 and 8
    ("device_ns_per_scanned_row",
     {7: _scan(cached=30, rows=1000), 8: _scan(cached=5, rows=500),
      9: _scan(cached=30, rows=1000), 10: _scan(cached=5, rows=500)},
     3.0e9 / 1500),
    ("device_ns_per_scanned_row", {7: _scan(), 8: _scan()}, None),
])
def test_scan_counter_metrics(bench, monkeypatch, metric, counts, want):
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, _window(counts)])
    got = bench.harness.metric_reader(metric)(RUN)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", ["scan_cache_hit_pct",
                                    "device_ns_per_scanned_row"])
def test_scan_counter_metrics_without_a_window(bench, monkeypatch, metric):
    """A rehearsal (no chip: ``peaks`` is None) has no window."""
    monkeypatch.setattr(bench.span_reduce, "_LAST", [None, None])
    run = dict(RUN, peaks=None)
    assert bench.span_reduce.window(run) is None
    assert bench.harness.metric_reader(metric)(run) is None


def test_device_ns_per_scanned_row_without_a_trace(bench, monkeypatch):
    run = dict(RUN, trace=None)
    counts = {7: _scan(cached=30, rows=1000)}
    monkeypatch.setattr(bench.span_reduce, "_LAST", [run, _window(counts)])
    assert bench.harness.metric_reader(
        "device_ns_per_scanned_row")(run) is None
    assert bench.harness.metric_reader(
        "scan_cache_hit_pct")(run) == 100.0
