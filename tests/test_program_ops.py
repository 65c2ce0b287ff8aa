"""The profiler's program table (``paddle_tpu/profiler/programs.py``): which
``jax.named_scope`` and which pass each HLO instruction of a compiled
program came from, who registers, that the table outlives its owner, and
the one join that turns (program, instruction, seconds) into seconds by
scope and by pass. CPU, tiny sizes: counts and names, never a time."""
import gc
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.core import telemetry
from paddle_tpu.jit import compile_watchdog
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import llama_tiny_config
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.profiler import programs as P


# ------------------------------------------------------------- the rule

def test_the_rule_is_the_data_the_real_programs_print():
    """What jax 0.9's ``as_text()`` of the real train step and segment
    program showed: the tokens the classification rests on."""
    assert P.PASS_TOKENS == (("recompute", "rematted_computation"),
                             ("backward", "transpose("),
                             ("forward", "jvp("))
    assert P.CLONE_TOKEN == ".remat"
    assert {"while", "body", "cond", "checkpoint", "closed_call",
            "rematted_computation", "pallas_call"} <= P.BARE
    assert {"jvp", "transpose"} <= set(P.TRANSFORMS)
    assert set(P.CALLS) == {"jit", "pjit"}


@pytest.mark.parametrize("path,scopes,pass_", [
    # the real train step's paths (tiny LLaMA, use_recompute, jax 0.9)
    ("jit(one_step)/jvp(attn)/flash_fwd/while/body/mul",
     ("attn", "flash_fwd"), "forward"),
    ("jit(one_step)/jvp(mlp)/jit(run)/jit(silu)/logistic",
     ("mlp",), "forward"),
    ("jit(one_step)/transpose(jvp(jvp()))/checkpoint/attn/jit(run)/"
     "fused_rope/while/body/jit(_where)/select_n",
     ("attn", "fused_rope"), "backward"),
    ("jit(one_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "mlp/jit(run)/dot_general", ("mlp",), "recompute"),
    ("jit(one_step)/transpose(jvp(lm_head))/jit(run)/while/body/"
     "closed_call/dot_general", ("lm_head",), "backward"),
    ("jit(one_step)/optimizer/sub", ("optimizer",), "none"),
    ("jit(one_step)/jvp(jit(run))/jit(_take)/gather", (), "forward"),
    # serving programs differentiate nothing
    ("jit(segment)/while/body/closed_call/attn/paged_attention/pallas_call",
     ("attn", "paged_attention"), "none"),
    ("jit(segment)/while/body/closed_call/sample/argmax", ("sample",),
     "none"),
    ("jit(segment)/while/body/closed_call/attn/cond/branch_1_fun/add",
     ("attn",), "none"),
    # nested scopes, outermost first
    ("jit(segment)/while/body/closed_call/mamba/ssm/ssd_decode/pallas_call",
     ("mamba", "ssm", "ssd_decode"), "none"),
    ("jit(f)/jvp(mamba/ssm)/mul", ("mamba", "ssm"), "forward"),
    ("jit(f)/moe/moe_route/jit(top_k)/top_k", ("moe", "moe_route"), "none"),
    # merged metadata: the first path files the instruction
    ("jit(f)/transpose(jvp(lm_head))/mul;jit(f)/jvp(attn)/add",
     ("lm_head",), "backward"),
    # what XLA inserted or a parameter: nothing
    ("", (), "none"),
    ("params['lm_head.weight']", (), "none"),
], ids=lambda v: v if isinstance(v, str) and "/" not in v else None)
def test_classify(path, scopes, pass_):
    rec = P.classify(path)
    assert rec["scopes"] == scopes and rec["pass"] == pass_
    assert rec["compiler_clone"] is False


def test_compiler_clone_is_the_instructions_own_name():
    path = "jit(one_step)/transpose(jvp(jvp()))/checkpoint/mlp/dot_general"
    assert P.classify(path, "fusion.640.remat")["compiler_clone"]
    assert P.classify(path, "fusion.625.remat2")["compiler_clone"]
    assert not P.classify(path, "fusion.640")["compiler_clone"]
    ops = P.parse_hlo_text(
        'ENTRY %main {\n'
        '  %fusion.640 = f32[2]{0} fusion(%p), kind=kLoop, '
        f'metadata={{op_name="{path}" stack_frame_id=3}}\n'
        '  ROOT %fusion.640.remat = f32[2]{0} fusion(%p), kind=kLoop, '
        f'metadata={{op_name="{path}"}}\n'
        '  %copy.3 = f32[2]{0} copy(%fusion.640)\n}\n')
    assert set(ops) == {"fusion.640", "fusion.640.remat", "copy.3"}
    assert ops["fusion.640.remat"]["compiler_clone"]
    assert not ops["fusion.640"]["compiler_clone"]
    assert ops["fusion.640"]["scopes"] == ("mlp",)
    assert ops["copy.3"]["scopes"] == () and ops["copy.3"]["pass"] == "none"


def test_a_fusion_is_one_instruction_and_says_when_it_mixed():
    """The optimizer's update in the epilogue of a weight-gradient product
    (the real train step compiles 36 such): filed under the fusion's own
    metadata, all of it, and flagged."""
    grad = "jit(one_step)/transpose(jvp(jvp()))/checkpoint/mlp/dot_general"
    ops = P.parse_hlo_text(
        "HloModule jit_one_step\n\n"
        "%fused_computation.7 (p0: f32[2], p1: f32[2]) -> f32[2] {\n"
        "  %p0 = f32[2]{0} parameter(0)\n"
        "  %p1 = f32[2]{0} parameter(1)\n"
        f'  %dot.1 = f32[2]{{0}} multiply(%p0, %p1), metadata={{op_name="{grad}"}}\n'
        '  ROOT %sub.2 = f32[2]{0} subtract(%p1, %dot.1), '
        'metadata={op_name="jit(one_step)/optimizer/sub"}\n}\n\n'
        "%fused_computation.8 (p0: f32[2]) -> f32[2] {\n"
        "  %p0.1 = f32[2]{0} parameter(0)\n"
        f'  ROOT %neg.3 = f32[2]{{0}} negate(%p0.1), metadata={{op_name="{grad}"}}\n}}\n\n'
        "ENTRY %main (a: f32[2], b: f32[2]) -> (f32[2], f32[2]) {\n"
        "  %a = f32[2]{0} parameter(0)\n  %b = f32[2]{0} parameter(1)\n"
        "  %fusion.1937 = f32[2]{0} fusion(%a, %b), kind=kOutput, "
        f'calls=%fused_computation.7, metadata={{op_name="{grad}"}}\n'
        "  %fusion.1938 = (f32[2]{0}, f32[2]{0}) fusion(%a), kind=kLoop, "
        f'calls=%fused_computation.8, metadata={{op_name="{grad}"}}\n'
        "  ROOT %t = (f32[2], f32[2]) tuple(%fusion.1937, %a)\n}\n")
    assert ops["fusion.1937"]["mixed"] and not ops["fusion.1938"]["mixed"]
    assert ops["fusion.1937"]["scopes"] == ("mlp",)
    assert ops["fusion.1937"]["pass"] == "backward"
    assert ops["sub.2"]["scopes"] == ("optimizer",) and not ops["a"]["mixed"]


# a toy with every transform the rule names: named scopes (one nested),
# jax.checkpoint a layer, value_and_grad, and an optimizer scope

def _toy_step():
    def layer(x, w):
        with jax.named_scope("attn"):
            x = x + jnp.tanh(x @ w["a"])
        with jax.named_scope("mamba"):
            with jax.named_scope("ssm"):
                x = x + jnp.sin(x @ w["m"])
        return x

    def loss(ws, x):
        x, _ = jax.lax.scan(
            lambda x, w: (jax.checkpoint(layer)(x, w), None), x, ws)
        with jax.named_scope("lm_head"):
            return jnp.mean(x ** 2)

    def step(ws, x):
        value, grads = jax.value_and_grad(loss)(ws, x)
        with jax.named_scope("optimizer"):
            ws = jax.tree_util.tree_map(lambda w, g: w - 0.1 * g, ws, grads)
        return value, ws

    ws = {"a": jnp.ones((3, 16, 16)), "m": jnp.ones((3, 16, 16))}
    return jax.jit(step).lower(ws, jnp.ones((4, 16))).compile()


@pytest.fixture(scope="module")
def toy_ops():
    table = profiler.ProgramTable()
    table.note("toy", _toy_step())
    assert table.programs() == [("jit_step", "toy")]
    return table.ops("jit_step")


@pytest.mark.parametrize("scope,pass_", [
    ("attn", "forward"), ("attn", "backward"), ("attn", "recompute"),
    ("mamba", "forward"), ("mamba", "backward"), ("mamba", "recompute"),
    ("lm_head", "forward"), ("lm_head", "backward"),
    ("optimizer", "none"),
])
def test_a_toy_step_holds_every_scope_under_every_pass(toy_ops, scope, pass_):
    hits = [r for r in toy_ops.values()
            if r["scopes"][:1] == (scope,) and r["pass"] == pass_]
    assert hits, sorted({(r["scopes"], r["pass"])
                         for r in toy_ops.values()})
    if scope == "mamba":      # nested: outermost first
        assert all(r["scopes"][:2] == ("mamba", "ssm") for r in hits)


def test_a_toy_steps_optimizer_has_no_pass_and_scopes_are_only_ours(toy_ops):
    assert not [r for r in toy_ops.values()
                if r["scopes"][:1] == ("optimizer",) and r["pass"] != "none"]
    seen = {s for r in toy_ops.values() for s in r["scopes"]}
    assert seen == {"attn", "mamba", "ssm", "lm_head", "optimizer"}


# ------------------------------------------------------------ the engine

@pytest.fixture(scope="module")
def warmed():
    """A tiny engine warmed up into a table of its own (other tests of the
    worker compile engines too): what the table says while the engine
    lives, and what it says once the engine is deleted."""
    paddle.seed(1)
    table = profiler.ProgramTable()
    facts = {"table": table}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(P, "_TABLE", table)
        model = LlamaForCausalLM(llama_tiny_config())
        model.eval()
        eng = ContinuousBatchingEngine(model, max_slots=2, max_len=32,
                                       page_size=8, prompt_buckets=(8,))
        eng.warmup(segment=4)
        facts["names"] = set(re.findall(
            r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ",
            eng.compiled_programs()[("segment", 4)].as_text(), re.M))
        facts["keys"] = set(eng.compiled_programs())
        # nothing was asked yet: only the decode program's module is taken
        facts["kept_at_compile"] = [e.key for e in table._entries.values()
                                    if e.name is not None]
        facts["alive"] = table.programs()
        facts["prefill_merged"] = table.ops("jit_prefill")
        facts["prefill_each"] = [e.parsed() for e in table._entries.values()
                                 if e.name == "jit_prefill"]
        del eng, model
        gc.collect()
        # the front door, with the owner gone
        assert profiler.program_table() is table
        facts["segment_ops"] = profiler.program_ops("jit_segment")
        facts["names_after"] = set(profiler.program_ops())
    return facts


def test_warmup_registers_every_program_it_compiles(warmed):
    assert {k for _, k in warmed["alive"]} == warmed["keys"]
    assert ("jit_segment", ("segment", 4)) in warmed["alive"]
    assert {n for n, _ in warmed["alive"]} >= {"jit_prefill",
                                               "jit_chunk_step"}
    # at compile time the engine pays for the decode program alone
    assert warmed["kept_at_compile"] == [("segment", 4)]


def test_the_segment_table_outlives_the_engine_and_knows_every_instruction(
        warmed):
    ops = warmed["segment_ops"]
    assert warmed["names"] and warmed["names"] <= set(ops)
    scopes = {r["scopes"][0] for r in ops.values() if r["scopes"]}
    assert {"attn", "mlp", "lm_head", "sample", "embed",
            "final_norm"} <= scopes
    assert {r["pass"] for r in ops.values()} == {"none"}
    assert warmed["table"].ops("jit_never_compiled") is None


def test_a_program_asked_about_while_its_owner_lived_stays_and_others_go(
        warmed):
    """The admission programs are held weakly: ``jit_prefill`` was asked
    about while the engine lived, so it is parsed and stays; the programs
    nobody asked about went with the engine."""
    table = warmed["table"]
    assert warmed["names_after"] == {n for n, _ in warmed["alive"]}
    assert table.ops("jit_prefill") == warmed["prefill_merged"]
    gone = profiler.ProgramTable()
    model = LlamaForCausalLM(llama_tiny_config(num_hidden_layers=1))
    eng = ContinuousBatchingEngine(model, max_slots=1, max_len=16,
                                   page_size=8, prompt_buckets=(8,))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(P, "_TABLE", gone)
        eng.warmup(segment=2)
    del eng, model
    gc.collect()
    assert gone.programs() == [("jit_segment", ("segment", 2))]
    assert gone.ops("jit_prefill") is None


def test_same_named_programs_keep_only_what_they_agree_on(warmed):
    """``jit_prefill`` at two admission widths: an instruction name that
    one files under ``attn`` and the other under ``mlp`` is dropped (the
    trace cannot say which of the two ran); one they agree on stays."""
    each, merged = warmed["prefill_each"], warmed["prefill_merged"]
    assert len(each) == 2
    union = set(each[0]) | set(each[1])
    split = {i for i in set(each[0]) & set(each[1])
             if P._filed(each[0][i]) != P._filed(each[1][i])}
    assert split and set(merged) == union - split


# --------------------------------------------------------- the train step

def _tiny_step():
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=1, use_recompute=True, max_position_embeddings=256))
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters(),
                                 multi_precision=True, acc_dtype="bfloat16")
    step = paddle.jit.TrainStep(model, lambda loss: loss, opt)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 256, (2, 128)).astype(np.int32))
    return step, ids


def test_the_compiling_call_registers_the_step_and_compiles_nothing_twice(
        monkeypatch):
    table = profiler.ProgramTable()
    monkeypatch.setattr(P, "_TABLE", table)
    step, ids = _tiny_step()
    wd = compile_watchdog()
    added = []
    note = paddle.jit.TrainStep._note_program

    def counted(self, operands):
        before = wd.thread_compiles()[0]
        note(self, operands)
        added.append(wd.thread_compiles()[0] - before)

    monkeypatch.setattr(paddle.jit.TrainStep, "_note_program", counted)
    loss0 = float(step(ids, None, None, ids))
    assert added == [0]          # out of JAX's in-process cache
    assert table.programs() == [("jit_one_step", "one_step")]
    entry = table._entries["'one_step'"]
    loss1 = float(step(ids, None, None, ids))
    assert added == [0] and np.isfinite([loss0, loss1]).all()
    assert table._entries["'one_step'"] is entry       # nothing new
    del step
    gc.collect()
    ops = table.ops("jit_one_step")
    assert {r["pass"] for r in ops.values()} == set(P.PASSES)
    by = {(r["scopes"][0], r["pass"]) for r in ops.values() if r["scopes"]}
    assert {("optimizer", "none"), ("attn", "forward"), ("attn", "backward"),
            ("attn", "recompute"), ("mlp", "recompute"),
            ("lm_head", "forward"), ("embed", "forward"),
            ("final_norm", "backward")} <= by
    assert not [r for r in ops.values()
                if r["scopes"][:1] == ("optimizer",) and r["pass"] != "none"]


# ---------------------------------------------------- the table's bound

class _Text:
    """Stands for a compiled program that can only print itself."""

    def __init__(self, module, body="  %a.1 = f32[] add(%x, %y)\n"):
        self.text = f"HloModule {module}, is_scheduled=true\n\nENTRY %m {{\n" \
                    f"{body}}}\n"

    def as_text(self):
        return self.text


def test_the_table_is_bounded_and_says_what_it_lost():
    evicted = telemetry.counter("profiler.programs_evicted_total")
    before = evicted.value()
    table = profiler.ProgramTable(floor=3)
    for i in range(3):
        table.note(("prefill", i), _Text("jit_prefill"))
    table.note("seg", _Text("jit_segment"))
    assert evicted.value() == before + 1
    assert [k for _, k in table.programs()] == [("prefill", 1),
                                                ("prefill", 2), "seg"]
    assert table.ops("jit_prefill") is None        # never a partial answer
    assert set(table.ops("jit_segment")) == {"a.1"}
    assert table.ops()["jit_prefill"] is None
    # an owner that holds more programs makes the room it needs
    roomy = profiler.ProgramTable(floor=3)
    for i in range(5):
        roomy.note(("prefill", i), _Text("jit_prefill"), owner_programs=i + 1)
    assert len(roomy.programs()) == 5 and evicted.value() == before + 1
    # the program filed again makes its name whole again
    table.note(("prefill", 0), _Text("jit_prefill"))   # pushes (prefill, 1)
    assert table.ops("jit_prefill") is None
    table.note(("prefill", 1), _Text("jit_prefill"))   # pushes (prefill, 2)
    table.note(("prefill", 2), _Text("jit_prefill"))   # pushes seg
    assert set(table.ops("jit_prefill")) == {"a.1"}
    assert table.ops("jit_segment") is None


# --------------------------------------------------------------- the join

def _table():
    table = profiler.ProgramTable()
    body = (
        '  %fusion.1 = f32[] fusion(%p), metadata={op_name="jit(s)/jvp(attn)/dot_general"}\n'
        '  %fusion.2 = f32[] fusion(%p), metadata={op_name="jit(s)/transpose(jvp(attn))/dot_general"}\n'
        '  %fusion.3 = f32[] fusion(%p), metadata={op_name="jit(s)/transpose(jvp())/checkpoint/rematted_computation/mlp/dot_general"}\n'
        '  %fusion.3.remat = f32[] fusion(%p), metadata={op_name="jit(s)/transpose(jvp())/checkpoint/rematted_computation/mlp/dot_general"}\n'
        '  %fusion.4 = f32[] fusion(%p), metadata={op_name="jit(s)/optimizer/sub"}\n'
        '  %copy.5 = f32[] copy(%p)\n')
    table.note("one_step", _Text("jit_s", body))
    return table


def test_the_join_sums_to_the_whole_and_counts_what_it_cannot_place():
    table = _table()
    events = [("jit_s", "fusion.1", 1.0), ("jit_s", "fusion.1", 1.0),
              ("jit_s", "fusion.2", 3.0), ("jit_s", "fusion.3", 1.5),
              ("jit_s", "fusion.3.remat", 0.5), ("jit_s", "fusion.4", 1.0),
              ("jit_s", "copy.5", 1.0), ("jit_s", "fusion.99", 1.0),
              ("jit_other", "fusion.1", 7.0)]
    out = profiler.attribute_device_time(events, table.ops)
    assert out["jit_other"] is None
    s = out["jit_s"]
    assert s["seconds"] == 10.0 and s["unmatched"] == 1.0
    assert s["compiler_clone"] == 0.5 and s["mixed"] == 0.0
    assert s["by_scope"] == {"attn": 5.0, "mlp": 2.0, "optimizer": 1.0,
                             "": 1.0}
    assert s["by_pass"] == {"forward": 2.0, "backward": 3.0,
                            "recompute": 2.0, "none": 2.0}
    assert s["by_scope_pass"]["attn/backward"] == 3.0
    assert s["unscoped_ops"] == {"copy.5": 1.0}
    for table_ in (s["by_scope"], s["by_pass"], s["by_scope_pass"]):
        assert 100.0 * (sum(table_.values()) + s["unmatched"]) \
            / s["seconds"] == pytest.approx(100.0)


def test_the_join_says_nothing_of_an_evicted_program():
    table = profiler.ProgramTable(floor=2)
    for name in "abc":
        table.note(name, _Text("jit_" + name))
    out = profiler.attribute_device_time(
        [("jit_a", "a.1", 1.0), ("jit_b", "a.1", 2.0)], table.ops)
    assert out["jit_a"] is None and out["jit_b"]["seconds"] == 2.0


def test_self_seconds_gives_a_loop_what_its_body_leaves():
    rows = P.self_seconds(
        [(0, 100, "jit_segment"), (200, 300, "jit_prefill")],
        [(10, 80, "%while.2 = (s32[]) while(%t), body=%b"),
         (20, 30, "%fusion.7 = f32[] fusion(%x)"), (50, 10, "copy.3"),
         (210, 5, "fusion.7"), (400, 5, "fusion.8"),
         # an async copy that the product after it outlasts
         (500, 10, "copy-start.4"), (505, 20, "fusion.9")])
    assert rows == [["jit_segment", "while.2", 40],
                    ["jit_segment", "fusion.7", 30],
                    ["jit_segment", "copy.3", 10],
                    ["jit_prefill", "fusion.7", 5], ["?", "fusion.8", 5],
                    ["?", "copy-start.4", 0], ["?", "fusion.9", 20]]


# ------------------------------------------------------------ the profiler

def test_profiler_summary_on_a_cpu_says_so_and_writes_where_it_is_told(
        tmp_path, capsys, monkeypatch):
    monkeypatch.delenv("PADDLE_PROFILER_LOGDIR", raising=False)
    with profiler.Profiler(log_dir=str(tmp_path / "trace")) as p:
        jnp.ones(4).block_until_ready()
        p.step()
    assert p.log_dir == str(tmp_path / "trace") and p.python_tracer is False
    assert p.summary() is None
    out = capsys.readouterr().out
    assert "no device plane" in out and "host events recorded" not in out
    with profiler.Profiler(timer_only=True) as q:
        q.step()
    assert q.log_dir is None and q.summary() is None
    monkeypatch.setenv("PADDLE_PROFILER_LOGDIR", str(tmp_path / "env"))
    with profiler.Profiler() as r:
        pass
    assert r.log_dir == str(tmp_path / "env")
