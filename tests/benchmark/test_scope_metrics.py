"""The scope-read layer metrics (PR 37), on the CPU: the ten readers over
``benchmark/layers/scope_ops.py`` on a record whose operations carry the names
of a tiny cell's own compiled step, the manifest's entries for them, and that
the two newest cells report exactly what they did.  A file of its own beside
``test_benchmark.py``, whose helpers it borrows: a PR that changes the program
adds files to the benchmark and edits none."""

import pytest

from benchmark import run  # no jax at import
from tests.benchmark.test_benchmark import (  # noqa: F401  (clock: a fixture)
    _manifest, _recorded_steps, _tiny_cell, clock,
)

THREE = ["alexnet_live", "bert_mlm", "laguna_train_s8k"]
TWO = ["bert_mlm", "laguna_train_s8k"]
# name: (unit, better, layer, cells).  The four that speak of laguna_train_s8k
# alone list bert_mlm beside it, where they read 0.0: test_laguna.py holds
# the entries whose list is exactly its cell to the ones PR 29 brought
ENTRIES = {
    "scope_coverage": ("%", "higher", "Compiled step", THREE),
    "optimizer_ms": ("ms", "lower", "Compiled step", THREE),
    "loss_ms": ("ms", "lower", "Compiled step", THREE),
    "recompute_ms": ("ms", "lower", "Compiled step", TWO),
    "mixer_ms": ("ms", "lower", "Compiled step", TWO),
    "mixer_glue_ms": ("ms", "lower", "Compiled step", TWO),
    "expert_route_ms": ("ms", "lower", "Expert layer", TWO),
    "expert_rows_ms": ("ms", "lower", "Expert layer", TWO),
    "expert_products_ms": ("ms", "lower", "Expert layer", TWO),
    "lrn_pool_ms": ("ms", "lower", "Compiled step", ["alexnet_live"]),
}
_SHARED = {
    "input_wait_share", "dispatch_ms", "device_step_ms", "mfu_device",
    "device_idle_share", "feed_source_ms", "feed_h2d_ms", "feed_backpressure_ms",
}


def _read(metric, recorded):
    folder = run.load_cell("laguna_train_s8k")["layers_dir"]
    return run.metric_reader(folder, metric)(recorded)


def test_manifest_entries_are_the_issues_appended_last():
    per_layer = _manifest()["per_layer"]
    assert [m["name"] for m in per_layer[-len(ENTRIES):]] == list(ENTRIES)
    for m in per_layer[-len(ENTRIES):]:
        unit, better, layer, cells = ENTRIES[m["name"]]
        assert (m["unit"], m["better"], m["layer"], m["workloads"]) == (
            unit, better, layer, cells), m
        assert m["source"] == "device_trace" and m["moves"] == "samples_per_s"
        assert set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}


@pytest.mark.parametrize("cell,own", [
    ("ling_train_s16k", {"kda_scan_ms", "kda_scan_roofline", "mla_attention_ms",
                         "mla_attention_roofline", "grouped_route_ms"}),
    ("mellum_train_packed8k", {"doc_attention_ms", "doc_attention_roofline"}),
])
def test_the_two_newest_cells_report_exactly_what_they_did(cell, own):
    reported = {m["name"] for m in run.load_cell(cell)["per_layer"]}
    assert reported == own | _SHARED
    assert not reported & set(ENTRIES)


def test_the_three_listed_cells_gain_their_entries_and_no_other():
    for cell in THREE:
        reported = {m["name"] for m in run.load_cell(cell)["per_layer"]}
        assert reported & set(ENTRIES) == {
            name for name, entry in ENTRIES.items() if cell in entry[3]
        }


# ------------------------------- the readers, on a tiny decoder's own step

@pytest.fixture(scope="module")
def scoped_run():
    """A record as ``run_cell`` hands the readers one: the trace's
    operations are named by the instructions of the tiny decoder's own
    compiled step (one ms each, two steps), beside a container, a name of
    another program and an operation with no text."""
    from sparknet_tpu.apps import lm_app

    solver, batches, _ = lm_app.build(lm_app.parser().parse_args([
        "--config", "tiny", "--seq-len", "32", "--batch-size", "2",
        "--synthetic-tokens", "4096",
    ]))
    solver.lower_step(next(iter(batches)))  # as run.step_program does
    table = solver.step_scopes()
    seconds = {f"%{name} = f32[8]{{0}} fusion(%x)": 2e-3 for name in table}
    seconds["%while.9 = (s32[], f32[8]{0}) while(%t), body=%b"] = 0.5
    seconds["%of.another.program = f32[8]{0} add(%x, %y)"] = 4e-3
    recorded = {"trace": {
        "op_seconds": seconds, "steps": 2, "program": "jit_fused(1)",
        "device_step_s": [1e-3 * len(table) + 2e-3] * 2,
    }}
    return solver, table, recorded


def _sum(table, keep):
    return float(sum(1 for e in table.values() if e.chain and keep(e)))


@pytest.mark.parametrize("metric,keep", [
    ("optimizer_ms", lambda e: "optimizer" in e.chain),
    ("loss_ms", lambda e: "lm_head" in e.chain or "loss" in e.chain),
    ("recompute_ms", lambda e: e.pass_ == "recompute"),
    ("mixer_ms", lambda e: e.chain[0].startswith("attn")),
    ("mixer_glue_ms", lambda e: e.chain[0].startswith("attn") and not e.kernel),
    ("expert_route_ms", lambda e: "moe.route" in e.chain),
    ("expert_rows_ms", lambda e: "moe.rows" in e.chain),
    ("expert_products_ms",
     lambda e: "moe.experts" in e.chain and "moe.rows" not in e.chain),
    ("lrn_pool_ms", lambda e: e.chain[0].startswith(("lrn.", "pooling."))),
], ids=lambda x: x if isinstance(x, str) else "")
def test_reader_sums_the_steps_own_instructions_by_scope(scoped_run, metric, keep):
    _solver, table, recorded = scoped_run
    value = _read(metric, recorded)
    assert value == pytest.approx(_sum(table, keep))
    if metric == "lrn_pool_ms":
        assert value == 0.0  # a number, not None: nothing of the kind ran
    else:
        assert value > 0


def test_coverage_leaves_containers_out_and_counts_what_joins_nothing(
    scoped_run, capsys
):
    _solver, table, recorded = scoped_run
    recorded.pop("scope_time", None)  # as a fresh run: the table is said once
    scoped = _sum(table, lambda e: True)
    total = len(table) + 2.0  # the stranger's 2 ms a step; the while's 250 not
    assert _read("scope_coverage", recorded) == pytest.approx(100 * scoped / total)
    said = capsys.readouterr().out
    assert "bench: scopes: device time by scope chain" in said
    assert "moe.experts/moe.rows" in said and "unjoined" in said
    # the parts add up to the device's step (here: made so)
    reduced = recorded["scope_time"]
    assert reduced["total"] == pytest.approx(
        1e3 * recorded["trace"]["device_step_s"][0])
    assert reduced["containers"] == pytest.approx(250.0)
    assert reduced["unjoined"] == pytest.approx(2.0)
    _read("optimizer_ms", recorded)
    assert "bench: scopes" not in capsys.readouterr().out  # once a run


def test_a_thin_coverage_is_said_on_a_bench_line(scoped_run, capsys):
    _solver, _table, recorded = scoped_run
    thin = {"trace": {**recorded["trace"], "op_seconds": {
        "%of.another.program = f32[8]{0} add(%x, %y)": 1.0}}}
    assert _read("scope_coverage", thin) == 0.0
    assert _read("optimizer_ms", thin) == 0.0
    assert "bench: scopes: only 0.00% of the device's time" in capsys.readouterr().out


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_readers_return_nothing_without_a_trace_or_a_table(
    scoped_run, metric, monkeypatch
):
    """An untraced run; a program that publishes no table (the parent
    commit has no ``step_scopes``; a solver that lowered nothing)."""
    from sparknet_tpu.utils import profiling

    _solver, _table, recorded = scoped_run
    assert _read(metric, {}) is None
    fresh = {"trace": dict(recorded["trace"])}
    monkeypatch.setattr(profiling, "step_scopes", lambda: None)
    assert _read(metric, fresh) is None
    monkeypatch.delattr(profiling, "step_scopes")
    assert _read(metric, {"trace": dict(recorded["trace"])}) is None


def test_traced_rehearsal_reports_a_number_for_each_of_the_cells_entries(
    clock, tmp_path, monkeypatch
):
    """``alexnet_live`` tiny with the recorded chip trace in the profiler's
    place: the trace's names are another program's (a few coincide, most
    join nothing), and every scope metric of the cell still reads a number
    and the run stays correct."""
    monkeypatch.setattr(run, "traced_steps", _recorded_steps)
    cell = _tiny_cell("alexnet_live")
    out = run.run_cell(
        cell, seed=11, seconds=0.5, trace=True, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 197e12},
    )
    assert out["correct"] is True, out
    for name in ("scope_coverage", "optimizer_ms", "loss_ms", "lrn_pool_ms"):
        assert out["metrics"][name]["value"] >= 0.0, name
    assert out["metrics"]["scope_coverage"]["value"] < 50.0
    assert "mixer_ms" not in out["metrics"]  # not this cell's
