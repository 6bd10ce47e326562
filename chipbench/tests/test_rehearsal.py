"""The rest of a run after the look for a chip, on the CPU backend at
scale 0.01: the result's keys, the control, and the timed path broken
underneath (``correct`` must come out false each time).  Run on the
benchmark's cell and on a cell added the way a later PR adds one: a
configuration file, its queries, and two entries in a manifest (here a
copy of BENCHMARK.json in a temporary directory), no other file touched.
"""
import json
import os

import pyarrow.parquet as papq
import pytest

import reference
import run as harness

ADDED = "tpch_sf5_lineitem.power"
CELLS = ["tpcds_sf1_store.power", ADDED]
SCALE = 0.01
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
#: at scale 0.01 the selective store queries select nothing: these
#: seeds' answers hold float cells in both cells
SEED = 2147483659
#: workload -> the manifest that names it, where that is not BENCHMARK.json
MANIFEST: dict = {}


@pytest.fixture(autouse=True, scope="module")
def _no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(autouse=True, scope="module")
def _added_cell(tmp_path_factory):
    """BENCHMARK.json plus the entries of one more configuration and
    cell; ``load_cell`` finds everything else by name."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    config = harness.load_json(harness.HERE, "configs",
                               "tpch_sf5_lineitem.json")
    bench["configs"].append({
        "name": config["name"], "source": config["source"],
        "file": "chipbench/configs/tpch_sf5_lineitem.json",
        "reduced": sorted(config["reduced"]), "why": "rehearsal"})
    bench["workloads"].append({
        "name": ADDED, "config": config["name"], "traffic": "power",
        "chips": 1, "why": "rehearsal"})
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    MANIFEST[ADDED] = str(path)
    yield
    MANIFEST.clear()


def load_cell(workload):
    return harness.load_cell(workload, MANIFEST.get(workload))


def rehearse(workload, trace=False, seed=SEED):
    cell = load_cell(workload)
    return cell, harness.run_cell(cell, seed, 0.5, trace, scale=SCALE,
                                  device=dict(DEVICE))


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_result_line(workload, trace):
    cell, result = rehearse(workload, trace)
    assert list(result)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device",
            "compared"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= len(cell["config"]["queries"])
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    if trace:
        # no device plane on the CPU backend: the trace readers return
        # nothing and the harness leaves them out of the line
        assert set(result["metrics"]) == {
            "planner_ms", "flushes_per_query", "window_compiles",
            "setup_compile_s"}
    else:
        assert set(result["metrics"]) == {"queries_per_hour",
                                          "query_p95_s", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
    for v in result["compared"].values():
        assert v["value"] <= v["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    """The reference in float32, put in the program's place, fails the
    cell's ``max_rel_gap`` limit."""
    cell = load_cell(workload)
    config = cell["config"]
    data_dir = harness.ensure_data(cell["config_name"], config, SCALE, SEED)
    want, _ = reference.answers(cell["config_name"], config["queries"],
                                data_dir, config["precision"])
    low, _ = reference.answers(cell["config_name"], config["queries"],
                               data_dir, config["control_precision"])
    run = {"queries": [{"name": q, "rows": low[q]} for q in want]}
    compared = harness.verdict(run, want, config["limits"])
    assert compared["max_rel_gap"]["value"] > \
        3 * compared["max_rel_gap"]["limit"]
    assert not all(r["verified"] for r in run["queries"])


def _nudge(rows):
    """One float cell moved by a float32's rounding."""
    out = [list(r) for r in rows]
    for r in out:
        for i, v in enumerate(r):
            if isinstance(v, float) and v:
                r[i] = v * (1 + 6e-8)
                return [tuple(x) for x in out]
    return rows


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(workload, monkeypatch):
    from spark_rapids_tpu.api.dataframe import DataFrame
    real = DataFrame.collect
    monkeypatch.setattr(DataFrame, "collect",
                        lambda self: _nudge(real(self)))
    _cell, result = rehearse(workload)
    assert result["correct"] is False
    assert result["compared"]["max_rel_gap"]["value"] > \
        result["compared"]["max_rel_gap"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_rows_left_out_is_not_correct(workload, monkeypatch):
    """The fact table the engine scans holds half of the rows the
    configuration states; the reference reads the whole."""
    real = harness.start_engine

    def halved(config, data_dir):
        fact = max(config["tables"],
                   key=lambda t: config["tables"][t]["rows"])
        half_dir = os.path.join(data_dir, "halved")
        os.makedirs(half_dir, exist_ok=True)
        for t in config["tables"]:
            table = papq.read_table(os.path.join(data_dir, f"{t}.parquet"))
            if t == fact:
                table = table.slice(0, table.num_rows // 2)
            papq.write_table(table, os.path.join(half_dir, f"{t}.parquet"))
        return real(config, half_dir)
    monkeypatch.setattr(harness, "start_engine", halved)
    _cell, result = rehearse(workload)
    assert result["correct"] is False
    assert result["compared"]["wrong_cells"]["value"] > 0


def test_failed_query_counts(monkeypatch):
    real = harness.run_query
    calls = {"n": 0}
    cell = load_cell(ADDED)
    first_of_window = 1 + cell["mix"]["warmup_passes"] * len(
        cell["config"]["queries"])

    def flaky(session, name, text):
        calls["n"] += 1
        # the warm-up's queries pass; the window's first one raises
        if calls["n"] == first_of_window:
            return real(session, name, "select no_such_column from lineitem")
        return real(session, name, text)
    monkeypatch.setattr(harness, "run_query", flaky)
    _cell, result = rehearse(ADDED)
    assert result["failed"] == 1 and result["correct"] is False
    assert result["compared"]["unanswered"]["value"] == 1
