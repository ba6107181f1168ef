"""Attribution of device time to the program's named scopes, and the host
spans of ``Trainer.fit`` on the device trace's clock: a training run traced
with the CPU profiler, where each executor thread plays a device."""
import cpu_trace
import pytest

from harness import core, scopes
from harness import trace as TR

READERS = ["sparse_stacks_ms", "attention_ms", "head_ms", "layer_scan_ms",
           "optimizer_ms", "recompute_ms", "dst_grad_ms", "dst_select_ms"]
_, MODEL, _ = core.resolve(core.load_spec(), "train-dst", rehearse=True)
NAMES = scopes.names(MODEL)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Two ``fit`` steps, the second ending in the DST update, at the
    rehearsal size under the CPU profiler: the trace, the op_name of each
    instruction of the two programs compiled again, and their text."""
    _, model, traffic = core.resolve(core.load_spec(), "train-dst",
                                     rehearse=True)
    got = cpu_trace.traced_fit(model, traffic, 11,
                               str(tmp_path_factory.mktemp("trace")))
    texts = scopes.compiled_texts(model, traffic)
    return (got, {p: scopes.op_paths(x) for p, x in texts.items()}, texts)


def test_every_scope_is_attributed_and_the_parts_add_up(traced_run):
    (devices, modules, _, _), paths, _ = traced_run
    lo = min(e[1] for d in devices for e in d)
    hi = max(e[2] for d in devices for e in d)
    seconds = {p: {} for p in NAMES}
    for ops, mods in zip(devices, modules):
        got = scopes.attribute(ops, mods, lo, hi, paths, NAMES)
        for p, r in got.items():
            # the program that ran is the program compiled again
            assert r["unmatched"] == 0.0
            if not r["executions"]:
                continue
            execs = [m for m in mods if p in m[0]]
            held = sum(e - s for _, s, e in scopes.clip_leaves(ops, lo, hi)
                       if any(a <= s < b for _, a, b in execs)) / 1e9
            assert sum(r["seconds"].values()) == pytest.approx(held)
            for k, v in r["seconds"].items():
                seconds[p][k] = seconds[p].get(k, 0.0) + v
            seconds[p]["remat"] = seconds[p].get("remat", 0.0) + r["remat"]
    for p, names in NAMES.items():
        for name in (*names, "remat"):
            assert seconds[p].get(name, 0.0) > 0, (p, name, seconds[p])
    # the model's scopes inside the DST gradient count as it
    assert set(seconds["dst_step"]) <= {*NAMES["dst_step"], "other",
                                        "remat"}


def test_trainer_spans_nest_in_their_step_on_the_device_clock(traced_run):
    (_, _, spans, runs), _, _ = traced_run
    steps = sorted(s for s in spans if s[0] == "trainer.step")
    assert len(steps) == 2
    for name, s, e in spans:
        assert any(a <= s and e <= b for _, a, b in steps), name
    names = {n for n, _, _ in spans}
    assert {"trainer.next_batch", "trainer.dispatch_step", "trainer.dst_due",
            "trainer.dispatch_dst", "trainer.log"} <= names
    # each dispatch starts before the operations of the program run it
    # dispatched: host spans and device events share one clock
    dispatch = sorted(s for n, s, _ in spans if n == "trainer.dispatch_step")
    ran = sorted(s for (m, _), s in runs.items() if "train_step" in m)
    assert len(ran) == len(dispatch) == 2
    for d, r in zip(dispatch, ran):
        assert d < r
    dst = [s for n, s, _ in spans if n == "trainer.dispatch_dst"]
    dst_ran = [s for (m, _), s in runs.items() if "dst_step" in m]
    assert len(dst) == len(dst_ran) == 1 and dst[0] < dst_ran[0]


@pytest.mark.parametrize("path,scope", [
    ("jit(train_step)/jvp(blocks)/while/body/closed_call/sparse/dot_general",
     "sparse"),
    ("jit(train_step)/transpose(jvp(blocks))/while/body/checkpoint/"
     "rematted_computation/attention/sparse/dot_general", "sparse"),
    ("jit(train_step)/jvp(blocks)/while/body/closed_call/attention/exp",
     "attention"),
    ("jit(train_step)/jvp(blocks)/while/body/dynamic_update_slice", "blocks"),
    ("jit(train_step)/transpose(jvp(head))/while/body/checkpoint/dot_general",
     "head"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/split", "other"),
    ("", "other"),
])
def test_scope_of_a_train_step_path(path, scope):
    assert scopes.scope_of(path, NAMES["train_step"]) == scope


def test_a_dst_path_counts_as_its_own_scope():
    path = ("jit(dst_step)/dst_grad/transpose(jvp(blocks))/while/body/"
            "checkpoint/sparse/dot_general")
    assert scopes.scope_of(path, NAMES["dst_step"]) == "dst_grad"


def test_attribution_of_events_by_module_execution():
    paths = {"train_step": {"fusion.1": ("jit(train_step)/jvp(blocks)/while/"
                                         "body/closed_call/sparse/dot_general",
                                         "own"),
                            "fusion.2": ("jit(train_step)/optimizer/mul",
                                         "operand"),
                            "copy.3": ("", "none")},
             "dst_step": {"fusion.1": ("jit(dst_step)/dst_select/sort",
                                       "loop")}}
    modules = [("jit_train_step(7)", 0, 100), ("jit_dst_step(8)", 100, 150),
               ("jit_train_step(7)", 200, 300)]
    ops = [("while.9", 0, 60), ("fusion.1", 10, 40), ("fusion.2", 60, 90),
           ("copy.3", 90, 95), ("fusion.1", 110, 140), ("fusion.4", 210, 220),
           ("fusion.1", 160, 170)]           # in no program execution
    got = scopes.attribute(ops, modules, 0, 1000, paths, NAMES)
    tr, dst = got["train_step"], got["dst_step"]
    assert tr["executions"] == 2 and dst["executions"] == 1
    assert tr["module_s"] == pytest.approx(200e-9)
    assert tr["seconds"] == pytest.approx(
        {"sparse": 30e-9, "optimizer": 30e-9, "other": 15e-9})
    assert tr["unmatched"] == pytest.approx(10e-9)    # fusion.4
    assert dst["seconds"] == pytest.approx({"dst_select": 30e-9})
    rules = {"sparse": {"own": 30e-9}, "optimizer": {"operand": 30e-9},
             "other": {"none": 5e-9, "unmatched": 10e-9}}
    assert tr["rules"].keys() == rules.keys()
    for scope, by_rule in rules.items():
        assert tr["rules"][scope] == pytest.approx(by_rule)
    assert dst["rules"].keys() == {"dst_select"}
    assert dst["rules"]["dst_select"] == pytest.approx({"loop": 30e-9})


def test_op_paths_of_compiled_text():
    """An instruction's own op_name; a multi-output fusion, which has none,
    takes the one most of its fused instructions carry; another without
    one takes that of the nearest operand with one, else that of the loop
    that holds it. Each comes with the rule that named it."""
    text = """\
%fc (p: f32[8]) -> (f32[8], f32[8]) {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/attention/mul"}
  %add.2 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/attention/mul"}
  %sub.3 = f32[8]{0} subtract(%p, %p), metadata={op_name="jit(f)/sub"}
  ROOT %tuple = (f32[8]{0}, f32[8]{0}) tuple(%mul.1, %sub.3)
}

%body (b: (s32[], f32[8])) -> (s32[], f32[8]) {
  %b = (s32[], f32[8]{0}) parameter(0)
  %gte.7 = f32[8]{0} get-tuple-element(%b), index=1
  %sort.8 = f32[8]{0} sort(f32[8]{0} %gte.7), dimensions={0}
  ROOT %tuple.9 = (s32[], f32[8]{0}) tuple(%gte.7, %sort.8)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fc.2, metadata={op_name="jit(f)/sparse/mul" stack_frame_id=2}
  %fusion.4 = (f32[8]{0}, f32[8]{0}) fusion(%x), kind=kLoop, calls=%fc
  %copy.5 = f32[8]{0} copy(f32[8]{0} %fusion.3)
  %sort.6 = f32[8]{0} sort(%copy.5), dimensions={0}
  %while.10 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/dst_select/while"}
  ROOT %copy.11 = f32[8]{0} copy(%x)
}
"""
    paths = scopes.op_paths(text)
    assert paths["fusion.3"] == ("jit(f)/sparse/mul", "own")
    assert paths["fusion.4"] == ("jit(f)/attention/mul", "fused")
    assert paths["copy.5"] == paths["sort.6"] == ("jit(f)/sparse/mul",
                                                  "operand")
    assert paths["sort.8"] == ("jit(f)/dst_select/while", "loop")
    assert paths["copy.11"] == paths["x"] == ("", "none")


def _executed_opcodes(text: str) -> dict[str, set[str]]:
    """Instruction name -> the opcodes it runs (its own, and for a fusion
    those of the instructions fused into it), for every instruction of a
    computation that is not a fusion's body: those a trace names."""
    comps, opcodes, called, fusion_bodies, comp = {}, {}, {}, set(), None
    for line in text.splitlines():
        if (c := scopes._COMPUTATION.match(line)):
            comp = c.group(1)
            comps[comp] = []
            continue
        if not (m := scopes._INSTR.match(line)):
            continue
        inst, rest = m.group(1), line[m.end():]
        opcodes[inst], _ = scopes._opcode_and_operands(rest)
        comps[comp].append(inst)
        called[inst] = scopes._CALLS.findall(rest)
        if opcodes[inst] == "fusion":
            fusion_bodies.update(called[inst])

    def runs(inst):
        out = {opcodes[inst]}
        if opcodes[inst] == "fusion":
            for c in called[inst]:
                for i in comps.get(c, ()):
                    out |= runs(i)
        return out

    return {i: runs(i) for c, insts in comps.items()
            if c not in fusion_bodies for i in insts}


@pytest.mark.parametrize("program,opcodes,allowed", [
    ("dst_step", {"sort"}, {"dst_select"}),
    ("dst_step", {"dot", "convolution"}, {"dst_grad"}),
    ("train_step", {"dot", "convolution"}, {"sparse", "attention", "head"}),
])
def test_compiled_instructions_land_in_their_scope(traced_run, program,
                                                   opcodes, allowed):
    """Whatever rule names them, the selection's sorts count as
    ``dst_select``, the gradient's matmuls as ``dst_grad``, and the train
    step's matmuls as the layer that holds them."""
    _, paths, texts = traced_run
    found = 0
    for inst, ran in _executed_opcodes(texts[program]).items():
        if ran & opcodes:
            found += 1
            path, rule = paths[program][inst]
            assert scopes.scope_of(path, NAMES[program]) in allowed, (
                inst, path, rule)
    assert found


@pytest.mark.parametrize("name", READERS)
def test_each_reader_gives_nothing_without_a_trace(name):
    reader = core.load_reader(name)
    assert reader.read(core.Outcome(metrics={}, checks=[], attempted=0,
                                    failed=0)) is None
    empty = TR.from_events([[("fusion.1", 0, 10)]], [[]], [])
    assert reader.read(core.Outcome(metrics={}, checks=[], attempted=0,
                                    failed=0, trace=empty)) is None


def test_the_readers_are_the_benchmarks_metrics():
    """Each reader is a per-layer metric of the benchmark that moves
    ``train_tok_s``, and every cell it lists exists and reports it."""
    spec = core.load_spec()
    cells = {w["name"] for w in spec["workloads"]}
    entries = {m["name"]: m for m in spec["per_layer"]}
    tok_s, = (m for m in spec["end_to_end"] if m["name"] == "train_tok_s")
    reporting = set(tok_s.get("workloads", cells)) & cells
    for name in READERS + ["host_syncs_per_step"]:
        assert entries[name]["moves"] == "train_tok_s"
        assert entries[name]["workloads"]
        assert set(entries[name]["workloads"]) <= reporting, name


def test_host_syncs_per_step_reads_the_programs_counter():
    reader = core.load_reader("host_syncs_per_step")
    out = lambda **c: core.Outcome(metrics={}, checks=[], attempted=0,
                                   failed=0, counters=c)
    assert reader.read(out(steps=74)) is None
    assert reader.read(out(steps=0, **{"program.host_syncs": 3})) is None
    assert reader.read(out(steps=74, **{"program.host_syncs": 150})) == \
        pytest.approx(150 / 74)


def test_op_paths_reads_an_instructions_continuation_lines():
    """A Pallas call prints its kernel_metadata over three lines and its
    op_name on the third, as do the get-tuple-elements of its results; the
    line after them is an instruction of its own."""
    text = r"""ENTRY %main (x: bf16[256,128]) -> bf16[256,128] {
  %x = bf16[256,128]{1,0} parameter(0)
  %iota.1 = s32[256,128]{1,0} iota(), iota_dimension=1, metadata={op_name="jit(train_step)/jvp(blocks)/while/body/iota"}
  %splash_mha_dq_no_residuals.1 = (f32[256,128]{1,0:T(8,128)}, bf16[2,256,128]{2,1,0:T(8,128)(2,1)}) custom-call(%x, %iota.1), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[256,128]{1,0}, s32[256,128]{1,0}}, frontend_attributes={kernel_metadata={
"xprof_metadata":"{"block_q_dq": 256, "block_kv_dq": 256, "q_layout": 1}"
}}, metadata={op_name="jit(train_step)/transpose(jvp(blocks))/while/body/attention/splash_mha_dq_no_residuals/pallas_call" stack_frame_id=15}, backend_config={"custom_call_config":{"body":"TUzvUgFN"}}
  %pallas_call.4 = bf16[2,256,128]{2,1,0:T(8,128)(2,1)} get-tuple-element(%splash_mha_dq_no_residuals.1), index=1, frontend_attributes={kernel_metadata={
"xprof_metadata":"{"block_q_dq": 256, "block_kv_dq": 256, "q_layout": 1}"
}}, metadata={op_name="jit(train_step)/transpose(jvp(blocks))/while/body/attention/splash_mha_dq_no_residuals/pallas_call" stack_frame_id=15}
  ROOT %copy.2 = bf16[256,128]{1,0} copy(%x), metadata={op_name="jit(train_step)/transpose(jvp(blocks))/while/body/copy"}
}
"""
    paths = scopes.op_paths(text)
    kernel = ("jit(train_step)/transpose(jvp(blocks))/while/body/attention/"
              "splash_mha_dq_no_residuals/pallas_call", "own")
    assert paths["splash_mha_dq_no_residuals.1"] == kernel
    assert paths["pallas_call.4"] == kernel
    assert scopes.scope_of(kernel[0], NAMES["train_step"]) == "attention"
    assert paths["copy.2"] == ("jit(train_step)/transpose(jvp(blocks))/"
                               "while/body/copy", "own")
    assert paths["x"] == ("", "none")
    assert [i for _, i, _ in scopes.instructions(text)] == [
        "x", "iota.1", "splash_mha_dq_no_residuals.1", "pallas_call.4",
        "copy.2"]


def test_busy_time_and_the_existing_readers_are_unchanged():
    """A pin of the reduction the accepted metrics read, on a fixed trace:
    two train steps, one DST update, gaps between them."""
    ms = 1_000_000
    modules = [[("jit_train_step(1)", 0, 700 * ms),
                ("jit_dst_step(2)", 703 * ms, 2903 * ms),
                ("jit_train_step(1)", 2906 * ms, 3606 * ms)]]
    ops = [[("while.1", 0, 600 * ms), ("fusion.2", 10 * ms, 590 * ms),
            ("fusion.3", 600 * ms, 700 * ms), ("sort.4", 703 * ms, 2903 * ms),
            ("fusion.2", 2906 * ms, 3606 * ms)]]
    spans = [("bench.window", 0, 3700 * ms),
             ("bench.dst_step", 0, 2904 * ms),
             ("bench.train_step", 2904 * ms, 3700 * ms)]
    out = core.Outcome(metrics={}, checks=[], attempted=2, failed=0,
                       trace=TR.from_events(ops, modules, spans),
                       counters={"window_s": 3.7, "flops": 2.0e15})
    out.peaks = type("P", (), {"bf16_flops": 197e12})()
    read = lambda n: core.load_reader(n).read(out)
    assert TR.busy_seconds(out.trace) == pytest.approx(3.6)
    assert read("train_step_ms") == pytest.approx(700.0)
    assert read("dst_update_ms") == pytest.approx(2200.0)
    assert read("idle_share.train") == pytest.approx(100 * 0.1 / 3.7)
    assert read("mfu.train") == pytest.approx(100 * 2.0e15 / 3.7 / 197e12)
    assert TR.idle_gaps(out.trace)[0] == ("bench.train_step",
                                          pytest.approx(0.094))
