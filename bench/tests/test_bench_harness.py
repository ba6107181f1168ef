"""The harness end to end on the CPU at the files' rehearsal sizes: each
cell's run, the refusal to run without a TPU, a cell, a metric and an
architecture added as new files alone, the controls, and the faults the
check must catch."""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import cpu_trace
import pytest

from harness import core
from harness import trace as TR

CELLS = ["train-dst"]


def _run(args, root=core.ROOT, cache=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cache:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    p = subprocess.run([sys.executable, str(root / "bench" / "run.py"), *args],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    return p.returncode, last, p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_each_cell(cell, tmp_path):
    rc, last, err = _run(["--workload", cell, "--seed", str(2**31 + 5),
                          "--seconds", "2", "--rehearse"], cache=tmp_path)
    assert rc == 0, err[-3000:]
    out = json.loads(last)
    assert out["correct"] is True, err[-3000:]
    assert out["device"]["platform"] == "cpu"
    # a CPU run prints counts, never a device metric
    assert set(out["metrics"]) == {"rehearsal_counts"}
    # the Trainer's counters are handed on: it waits on the step counter
    # and on whether the DST update is due, once each a step
    counts = out["metrics"]["rehearsal_counts"]
    assert counts["program.host_syncs"] >= 2 * counts["steps"] > 0
    assert counts["program.straggler_events"] >= 0
    assert list(out)[-1] == "checks"
    assert "programs compiled in the window: 0" in err


def test_a_run_without_a_tpu_fails_and_prints_nothing(tmp_path):
    rc, last, err = _run(["--workload", "train-dst", "--seed", "1",
                          "--seconds", "1", "--trace", "0"], cache=tmp_path)
    assert rc != 0
    assert last == ""
    assert "FAILED" in err


def test_without_the_program_a_run_fails(tmp_path):
    (tmp_path / "bench").mkdir()
    shutil.copy(core.ROOT / "BENCHMARK.json", tmp_path)
    for sub in ("harness", "configs", "traffic", "metrics"):
        shutil.copytree(core.BENCH / sub, tmp_path / "bench" / sub)
    shutil.copy(core.BENCH / "run.py", tmp_path / "bench")
    rc, last, _ = _run(["--workload", "train-dst", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], root=tmp_path)
    assert rc != 0 and last == ""


def _add_cell(root, spec, name, config, traffic, reader):
    """A traffic file, a reader of the cell's per-layer metric, and their
    entries in BENCHMARK.json."""
    (root / "bench" / "traffic" / f"{name}.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "metrics" / f"{name}_metric.py").write_text(reader)
    spec["workloads"].append({"name": f"train-{name}", "config": config,
                              "traffic": name, "chips": 1,
                              "why": "added as data alone"})
    spec["per_layer"].append({"name": f"{name}_metric", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "training step",
                              "moves": "train_tok_s",
                              "workloads": [f"train-{name}"]})


def _add_architecture(root, spec, model):
    """An architecture module and a configuration that names it."""
    shutil.copy(pathlib.Path(__file__).parent / "second_arch.py",
                root / "bench" / "harness" / "archs" / "qwen3_untied.py")
    model = dict(model, name="qwen3-untied", model_type="qwen3_untied",
                 tie_word_embeddings=False)
    (root / "bench" / "configs" / "qwen3-untied.json").write_text(
        json.dumps(model))
    spec["configs"].append({"name": "qwen3-untied",
                            "source": "https://huggingface.co/Qwen/Qwen3-1.7B",
                            "file": "bench/configs/qwen3-untied.json",
                            "reduced": ["num_hidden_layers"],
                            "why": "no qk-norm, untied head"})


def _untied_reads(root, spec, monkeypatch, tmp_path):
    """The added reader on a CPU-traced run of the added cell, in this
    process, with the copy's architecture module as the harness finds it."""
    name = "harness.archs.qwen3_untied"
    mod_spec = importlib.util.spec_from_file_location(
        name, root / "bench" / "harness" / "archs" / "qwen3_untied.py")
    mod = importlib.util.module_from_spec(mod_spec)
    monkeypatch.setitem(sys.modules, name, mod)
    mod_spec.loader.exec_module(mod)
    _, model, traffic = core.resolve(spec, "train-untied", root=root,
                                     rehearse=True)
    devices, modules, _, _ = cpu_trace.traced_fit(model, traffic, 5,
                                                  str(tmp_path / "trace"))
    trace = TR.from_events([sum(devices, [])], [sum(modules, [])], [])
    out = core.Outcome(metrics={}, checks=[], attempted=0, failed=0,
                       trace=trace, model=model, traffic=traffic)
    from harness import cli
    return cli.per_layer(spec, "train-untied", out, root=root)


@pytest.mark.parametrize("added", ["cell", "architecture"])
def test_a_cell_and_a_metric_added_as_data_alone(added, monkeypatch,
                                                 tmp_path):
    """A later change adds a traffic file, a reader and entries in
    BENCHMARK.json, or besides them an architecture module and its
    configuration; no file the harness had is edited."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(core.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(core.ROOT / "src")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads((core.ROOT / "BENCHMARK.json").read_text())
    dst = json.loads((core.BENCH / "traffic" / "dst.json").read_text())
    if added == "cell":
        dst["rehearsal"]["batch"] = 3
        _add_cell(root, spec, "dst3", "qwen3-1.7b-train-5l", dst,
                  "def read(out):\n    return float(out.attempted)\n")
        workload = "train-dst3"
    else:
        model = json.loads((core.BENCH / "configs"
                            / "qwen3-1.7b-train-5l.json").read_text())
        _add_architecture(root, spec, model)
        _add_cell(root, spec, "untied", "qwen3-untied", dst,
                  "from harness import scopes\n\n\ndef read(out):\n"
                  "    return scopes.ms_per_execution(out, 'train_step', "
                  "'attention')\n")
        workload = "train-untied"
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    args = ["--workload", workload, "--seed", "7", "--seconds", "1",
            "--rehearse"]
    rc, last, err = _run(args, root=root, cache=tmp_path / "cache")
    assert rc == 0, err[-3000:]
    assert json.loads(last)["correct"] is True, err[-3000:]
    if added == "architecture":
        rc, last, err = _run(args + ["--fault", "half"], root=root,
                             cache=tmp_path / "cache")
        assert rc == 0, err[-3000:]
        assert json.loads(last)["correct"] is False, err[-3000:]
        got = _untied_reads(root, spec, monkeypatch, tmp_path)
        assert got["untied_metric"]["value"] > 0
        assert got["untied_metric"]["unit"] == "ms"
    else:
        from harness import cli
        out = core.Outcome(metrics={}, checks=[], attempted=4, failed=0)
        got = cli.per_layer(spec, workload, out, root=root)
        assert got["dst3_metric"] == {"value": 4.0, "unit": "ms"}
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(cell, tmp_path):
    rc, last, err = _run(["--workload", cell, "--seed", "11", "--seconds",
                          "1", "--rehearse", "--control"], cache=tmp_path)
    assert rc == 0, err[-3000:]
    assert json.loads(last)["correct"] is False, err[-3000:]


# -- faults planted under the timed path ------------------------------------


def _drive(cell, monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    cell_, model, traffic = core.resolve(core.load_spec(), cell,
                                         rehearse=True)
    run = core.Run(workload=cell, seed=3, seconds=1.0, trace=False,
                   cell=cell_, model=model, traffic=traffic, chips=1,
                   rehearse=True, t_start=core.now())
    return core.loop(traffic).run(run)


def test_faults_are_sound_runs_when_unplanted(monkeypatch, tmp_path):
    assert _drive("train-dst", monkeypatch, tmp_path).correct


def _train_fault(kind):
    from repro.train import trainer as TRN
    orig = TRN.make_train_step

    def make(cfg, registry, lr_fn, **kw):
        step = orig(cfg, registry, lr_fn, **kw)

        def broken(state, batch):
            if kind == "half":
                half = batch["tokens"].shape[0] // 2
                batch = {k: v[:half] for k, v in batch.items()}
            new, metrics = step(state, batch)
            if kind == "unchanged":
                new = state
            return new, metrics

        return broken

    return make


@pytest.mark.parametrize("kind", ["unchanged", "half"])
def test_training_fault_is_caught(kind, monkeypatch, tmp_path):
    from repro.train import trainer as TRN
    monkeypatch.setattr(TRN, "make_train_step", _train_fault(kind))
    assert not _drive("train-dst", monkeypatch, tmp_path).correct


def _failed(out):
    return {c.name for c in out.checks if not c.ok}


def test_dst_update_leaving_the_masks_unchanged_is_caught(monkeypatch,
                                                          tmp_path):
    from repro.train import trainer as TRN
    orig = TRN.make_dst_step

    def make(cfg, registry, **kw):
        step = orig(cfg, registry, **kw)

        def broken(state, batch):
            new = step(state, batch)
            return new._replace(masks=state.masks,
                                neuron_active=state.neuron_active)

        return broken

    monkeypatch.setattr(TRN, "make_dst_step", make)
    out = _drive("train-dst", monkeypatch, tmp_path)
    assert {"dst_flip_gap", "dst_mask_mismatch"} <= _failed(out)


def test_dst_update_regrowing_at_random_is_caught(monkeypatch, tmp_path):
    import jax
    from repro.sparse import registry as REG
    orig = REG.dst_update

    def broken(cfg, registry, params, grads, state, drop, rng, **kw):
        noise = jax.tree.map(lambda g: jax.random.uniform(rng, g.shape),
                             grads)
        return orig(cfg, registry, params, noise, state, drop, rng, **kw)

    monkeypatch.setattr(REG, "dst_update", broken)
    out = _drive("train-dst", monkeypatch, tmp_path)
    assert _failed(out) == {"dst_mask_mismatch"}


@pytest.mark.parametrize("fault", ["half", "regrow_random"])
def test_planted_reference_fault_comes_out_not_correct(fault, tmp_path):
    rc, last, err = _run(["--workload", "train-dst", "--seed", "13",
                          "--seconds", "1", "--rehearse", "--fault", fault],
                         cache=tmp_path)
    assert rc == 0, err[-3000:]
    assert json.loads(last)["correct"] is False, err[-3000:]
