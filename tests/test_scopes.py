"""Device time by the program's own scopes (utils/profiling.py): the
vocabulary the program declares as jax traces it, the table read from a
compiled step's text, the reduction of a trace's seconds by it, the Solver's
``step_scopes`` and the line ``finish_run`` prints under ``--profile-dir``.
All on the CPU: the table is a pure function over text, and a trace's
operations are made up from the compiled step's own instruction names."""

import inspect
import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from sparknet_tpu.solver.trainer import Solver
from sparknet_tpu.utils import profiling
from sparknet_tpu.utils.profiling import Scoped, scope
from tests.test_fusion import _bert, _decoder, _prototxt


# ------------------------------------------------------------ the vocabulary

def test_scope_declares_its_name_and_takes_one_path_element():
    with scope("test.part"):
        pass
    assert "test.part" in profiling.declared_scopes()
    for bad in ("a/b", "jvp(a)", ""):
        with pytest.raises(ValueError):
            scope(bad)


def test_every_named_scope_of_the_program_goes_through_the_helper():
    """``jax.named_scope`` directly would reach the HLO and not the
    vocabulary: the join would skip it."""
    import glob
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "sparknet_tpu")
    direct = [
        path for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True)
        if "named_scope(" in open(path).read()
        and not path.endswith(os.path.join("utils", "profiling.py"))
    ]
    assert direct == []


# ------------------------------------------- the table, on a toy's own text

def _toy_text():
    """A scanned, checkpointed layer of two scopes (one nested), a head and
    an update: the compiled module's text."""

    def layer(w, x):
        with scope("toy.attn"):
            with scope("toy.proj"):
                y = x @ w
            y = jnp.tanh(y)
        with scope("toy.mlp"):
            return jax.nn.silu(y @ w.T)

    def step(ws, x, head):
        def loss(ws, head):
            h, _ = lax.scan(
                lambda h, w: (jax.checkpoint(layer)(w, h), None), x, ws
            )
            with scope("toy.head"):
                return jnp.mean((h @ head) ** 2)

        g, gh = jax.grad(loss, argnums=(0, 1))(ws, head)
        with scope("toy.update"):
            return ws - 0.1 * g, head - 0.1 * gh

    args = jnp.ones((3, 16, 16)), jnp.ones((8, 16)), jnp.ones((16, 4))
    return jax.jit(step).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def toy():
    text = _toy_text()
    return text, profiling.scope_table(text, profiling.declared_scopes())


def _own_op_names(text):
    """{instruction: its own op_name} over the module's text."""
    found = {}
    for line in text.splitlines():
        m = re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"", line)
        if m:
            found[m.group(1)] = m.group(2)
    return found


def test_table_reads_chain_and_pass_off_the_compiled_text(toy):
    text, table = toy
    names = _own_op_names(text)
    by_chain = {}
    for name, entry in table.items():
        by_chain.setdefault((entry.chain, entry.pass_), []).append(name)
    # the nested scope keeps both names, in all three passes
    for pass_ in ("forward", "recompute", "backward"):
        assert (("toy.attn", "toy.proj"), pass_) in by_chain, sorted(by_chain)
    # the pass is what the path says
    for name, entry in table.items():
        path = names.get(name, "")
        if "rematted_computation" in path:
            assert entry.pass_ == "recompute", (name, path)
        elif "transpose(" in path:
            assert entry.pass_ == "backward", (name, path)
        elif path:
            assert entry.pass_ == "forward", (name, path)
    # transpose(jvp(toy.head)) unwraps; the update is forward and alone
    assert (("toy.head",), "backward") in by_chain
    assert (("toy.head",), "forward") in by_chain
    assert {p for c, p in by_chain if c == ("toy.update",)} == {"forward"}
    # every instruction whose path names a scope got that scope
    for name, path in names.items():
        if name in table and "toy." in path.split(";")[0]:
            assert table[name].chain, (name, path)
    assert not any(entry.kernel for entry in table.values())  # no Pallas here


def test_containers_and_plumbing_get_no_entry_and_their_children_do(toy):
    text, table = toy
    opcodes = {}
    for line in text.splitlines():
        if " = " in line and line.startswith(" "):
            opcodes[profiling.instruction_name(
                line.strip().removeprefix("ROOT ")
            )] = profiling.opcode_of(line)
    assert "while" in opcodes.values()
    for name, op in opcodes.items():
        if op in ("while", "conditional", "call", "parameter", "constant",
                  "tuple", "get-tuple-element", "bitcast"):
            assert name not in table, (name, op)
    # the loop's body holds the layer: its dots are entries of their own
    assert sum(
        1 for n, e in table.items()
        if e.chain == ("toy.attn", "toy.proj") and opcodes[n] == "dot"
    ) >= 3


_CRAFTED = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %a = f32[8]{0} negate(%p0), metadata={op_name="jit(step)/jvp(one)/neg"}
  %b = f32[8]{0} exponential(%a), metadata={op_name="jit(step)/jvp(one)/exp"}
  ROOT %c = f32[8]{0} add(%a, %b), metadata={op_name="jit(step)/jvp(two)/add"}
}

%fused_computation.2 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %d = f32[8]{0} tanh(%p0.1), metadata={op_name="jit(step)/transpose(jvp(one))/inner/tanh"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %x = f32[8]{0:T(256)} get-tuple-element(%arg), index=1
  %kern = f32[8]{0:T(256)} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(one)/while/body/closed_call/inner/pallas_call"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%x, %kern)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (in: f32[8]) -> f32[8] {
  %in = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%in), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/transpose(jvp(one))/inner/tanh"}
  %init = (s32[], f32[8]{0}) tuple(%in, %fusion.2)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
  %lone = f32[8]{0} copy(%fusion.2)
  %fetch-start = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%in)
  %fetch-done = f32[8]{0:S(1)} copy-done(%fetch-start)
  %grouped.3 = f32[8]{0} custom-call(%fetch-done, /*index=1*/%in), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %act = f32[8]{0} negate(%grouped.3), metadata={op_name="jit(step)/jvp(two)/neg"}
  %stray = f32[8]{0} copy(%in)
  ROOT %out = f32[8]{0} multiply(%lone, %stray), metadata={op_name="jit(step)/mul"}
}
"""


def test_a_fusion_without_a_name_takes_its_bodys_majority_and_is_marked_mixed():
    table = profiling.scope_table(_CRAFTED, {"one", "two", "inner"})
    assert table["fusion.1"] == Scoped(("one",), "forward", False, True, False)
    # its own op_name speaks where it has one; one outermost scope: not mixed
    assert table["fusion.2"] == Scoped(
        ("one", "inner"), "backward", False, False, False)
    # a Pallas kernel inside a loop's body, under the scopes of its path
    assert table["kern"] == Scoped(("one", "inner"), "forward", True, False, False)
    assert table["out"] == Scoped((), "forward", False, False, False)
    assert set(table) == {
        "fusion.1", "fusion.2", "kern", "lone", "out", "fetch-start",
        "fetch-done", "grouped.3", "act", "stray"}


def test_what_the_compiler_made_is_lent_the_scope_of_what_uses_it():
    """XLA's own kernel for a grouped product (``op_name="ragged-dot-none"``,
    no path of the program's) and the prefetch of its operand take the scope
    of the instruction they serve; a copy whose user is unscoped takes its
    operand's; one with neither stays unscoped."""
    table = profiling.scope_table(_CRAFTED, {"one", "two", "inner"})
    assert table["grouped.3"] == Scoped(("two",), "forward", True, False, True)
    assert table["fetch-start"] == table["fetch-done"] == Scoped(
        ("two",), "forward", False, False, True)
    assert table["lone"] == Scoped(("one", "inner"), "backward", False, False, True)
    assert table["stray"] == Scoped((), "forward", False, False, False)
    assert not table["act"].lent and not table["fusion.1"].lent


def test_by_scope_adds_up_and_tells_unscoped_unjoined_mixed_and_containers():
    table = profiling.scope_table(_CRAFTED, {"one", "two", "inner"})
    seconds = {
        "%fusion.1 = f32[8]{0} fusion(%in), kind=kLoop": 0.004,
        "%fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop": 0.006,
        "%kern = f32[8]{0:T(256)} custom-call(%x)": 0.010,
        "%lone = f32[8]{0} copy(%fusion.2)": 0.002,
        "%while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond": 0.010,
        "%stranger.7 = f32[8]{0} add(%a, %b)": 0.001,
        "nameless": 0.001,
    }
    out = profiling.by_scope(seconds, table, steps=2)
    assert out["rows"] == {
        (("one",), "forward", False): pytest.approx(2.0),
        (("one", "inner"), "backward", False): pytest.approx(4.0),
        (("one", "inner"), "forward", True): pytest.approx(5.0),
    }
    assert out["unscoped"] == 0.0
    assert out["unjoined"] == pytest.approx(1.0)  # the stranger and the nameless
    assert out["mixed"] == pytest.approx(2.0)
    assert out["lent"] == pytest.approx(1.0)  # the copy, placed by its operand
    assert out["containers"] == pytest.approx(5.0)  # in nothing else
    leaves = sum(s for k, s in seconds.items() if " while(" not in k)
    assert out["total"] == pytest.approx(1e3 * leaves / 2)
    assert out["coverage"] == pytest.approx(100 * 11.0 / 12.0)
    said = "\n".join(profiling.scope_lines(out))
    assert re.search(r"^one\s+7\.000\s+4\.000\s+0\.000\s+11\.000\s+91\.67%\s+5\.000$",
                     said, re.M), said
    assert "unscoped" in said and "unjoined" in said and "mixed" in said
    assert "lent" in said
    whole = "\n".join(profiling.scope_lines(out, depth=None))
    assert "one/inner" in whole


def test_step_op_seconds_keeps_what_ran_inside_the_programs_executions():
    modules = [("jit_fused(1)", 100, 50), ("jit_other(2)", 200, 50),
               ("jit_fused(1)", 300, 50)]
    ops = [("%a = f32[] add()", 110, 10), ("%a = f32[] add()", 310, 20),
           ("%b = f32[] add()", 210, 30), ("%c = f32[] add()", 160, 5)]
    seconds, steps = profiling.step_op_seconds(modules, ops, "jit_fused(1)")
    assert steps == 2 and seconds == {"%a = f32[] add()": pytest.approx(30e-9)}


# ------------------------------------------------- the Solver's own programs

# the scopes PERF.md section 3 names for each family
_NAMED = {
    "prototxt": {"innerproduct.ip1", "relu.relu1", "innerproduct.ip2",
                 "softmaxwithloss.loss", "optimizer"},
    "bert": {"embed", "attn", "attn.proj", "mlp.dense", "norm", "loss",
             "optimizer"},
    "decoder": {"embed", "norm", "residual", "attn.full", "attn.window",
                "attn.proj", "attn.rope", "mlp.dense", "moe.route",
                "moe.experts", "moe.rows", "moe.shared", "lm_head", "loss",
                "optimizer"},
    "hybrid": {"embed", "norm", "residual", "attn.kda", "kda.scan", "attn.mla",
               "attn.proj", "attn.rope", "mlp.dense", "moe.route",
               "moe.experts", "moe.rows", "moe.shared", "lm_head", "loss",
               "optimizer"},
}


def _hybrid():
    from sparknet_tpu.apps import lm_app

    solver, batches, _ = lm_app.build(lm_app.parser().parse_args([
        "--config", "tiny_hybrid", "--seq-len", "64", "--batch-size", "2",
        "--synthetic-tokens", "4096",
    ]))
    return solver, batches


@pytest.fixture(scope="module", params=sorted(_NAMED))
def lowered_family(request):
    build = {"prototxt": _prototxt, "bert": _bert, "decoder": _decoder,
             "hybrid": _hybrid}[request.param]
    solver, batches = build()
    assert solver.step_scopes() is None  # nothing lowered yet
    assert profiling.step_scopes() is None  # ... and this is the newest solver
    lowered = solver.lower_step(next(iter(batches)))
    return request.param, solver, lowered, batches


def test_step_scopes_covers_the_compiled_step_and_holds_every_named_scope(
    lowered_family
):
    family, solver, lowered, _batches = lowered_family
    table = solver.step_scopes()
    assert table is solver.step_scopes()  # memoised for this Lowered
    assert profiling.step_scopes() is table  # what a caller without a solver gets
    seen = {s for entry in table.values() for s in entry.chain}
    assert seen >= _NAMED[family], _NAMED[family] - seen
    # of the instructions that carry a path of the program's (XLA:CPU adds
    # copies of the donated parameters and reduce-window rewrites that
    # carry none: no table could place them), 95 % lie under a scope
    named = set(_own_op_names(lowered.compile().as_text()))
    placed = [name for name in table if name in named]
    scoped = sum(bool(table[name].chain) for name in placed)
    assert len(placed) > 20 or family == "prototxt"
    assert scoped >= 0.95 * len(placed), (scoped, len(placed))
    if family != "prototxt":
        assert {e.pass_ for e in table.values()} >= {"forward", "backward"}
    if family == "hybrid":  # the KDA segments are checkpoints
        assert any(
            e.pass_ == "recompute" and "kda.scan" in e.chain for e in table.values()
        )


def test_by_scope_over_the_steps_own_names_adds_up_to_their_sum(lowered_family):
    _family, solver, _lowered, _batches = lowered_family
    table = solver.step_scopes()
    seconds = {f"%{name} = f32[] made-up()": 1e-3 for name in table}
    seconds["%not.in.the.table = f32[] add()"] = 5e-3
    out = profiling.by_scope(seconds, table, steps=1)
    assert out["unjoined"] == pytest.approx(5.0)
    assert sum(out["rows"].values()) + out["unscoped"] == pytest.approx(len(table))
    assert out["total"] == pytest.approx(len(table) + 5.0)


def test_a_new_lowering_gets_a_new_table(lowered_family):
    _family, solver, lowered, batches = lowered_family
    table = solver.step_scopes()
    try:
        assert solver.lower_step(next(iter(batches))) is not lowered
        fresh = solver.step_scopes()
        assert fresh is not table and fresh == table  # the same program
    finally:
        solver._lowered, solver._scopes = lowered, (lowered, table)


def test_the_step_path_reads_nothing_of_the_scope_table():
    """With tracing off nothing new runs per step: ``step`` and
    ``_dispatch`` do not touch what ``lower_step`` keeps."""
    for fn in (Solver.step, Solver._dispatch):
        source = inspect.getsource(fn)
        for word in ("_lowered", "_scopes", "profiling", "scope("):
            assert word not in source, (fn.__name__, word)


def test_a_prototxt_layer_is_a_scope_of_its_own_type_and_name():
    from sparknet_tpu.nets.xlanet import layer_scope
    from types import SimpleNamespace as Layer

    assert layer_scope(Layer(type="Convolution", name="conv1")) == "convolution.conv1"
    assert layer_scope(Layer(type="Pooling", name="inception_3a/pool(3x3)")) == (
        "pooling.inception_3a_pool_3x3_"
    )


# ------------------------------------------------- the operator's table

def test_finish_run_prints_the_device_time_by_scope_on_a_recorded_plane(
    lowered_family, monkeypatch, capsys
):
    """``--profile-dir`` alone: no ``--trace``, no timeline.  The plane is
    made of the lowered step's own instruction names, three executions."""
    from sparknet_tpu import telemetry

    family, solver, _lowered, _batches = lowered_family
    table = solver.step_scopes()
    profiling.publish_step_source(solver)  # as if it were the newest
    program, ops, modules = "jit_fused(123)", [], []
    for step in range(3):
        start = 1_000_000 * step
        modules.append((program, start, 900_000))
        for i, name in enumerate(sorted(table)[:200]):
            ops.append((f"%{name} = f32[] op()", start + 1000 * i, 1000))
    modules.append(("jit_sparknet_anchor(9)", 5_000_000, 100))
    monkeypatch.setattr(
        profiling, "device_modules", lambda d: {"/device:TPU:0": modules}
    )
    monkeypatch.setattr(profiling, "device_ops", lambda d: {"/device:TPU:0": ops})
    telemetry.install_for_training(solver, None, "/nowhere/prof")
    assert not solver.timeline.enabled  # --profile-dir alone installs none
    telemetry.finish_run()
    said = capsys.readouterr().out
    assert "trace: device time by scope, ms a step over 3 executions of "\
        "jit_fused(123)" in said
    assert "forward" in said and "unscoped" in said and "unjoined" in said
    assert "optimizer" in said and "mixed" in said
    counted = min(len(table), 200)
    assert f"a step {counted * 1e-3:.3f} ms on the device" in said
    # a second finish_run has no profile directory left: nothing printed
    telemetry.finish_run()
    assert "by scope" not in capsys.readouterr().out


def test_first_batch_lowered_lowers_once_and_passes_every_batch_on():
    from sparknet_tpu import telemetry

    solver, batches = _prototxt()
    plain = telemetry.first_batch_lowered(batches, solver, None)
    assert plain is batches and solver.step_scopes() is None
    wrapped = telemetry.first_batch_lowered(batches, solver, "/some/dir")
    solver.step(wrapped, 2)
    assert solver.iter == 2 and solver.step_scopes()
    assert "optimizer" in {s for e in solver.step_scopes().values() for s in e.chain}
