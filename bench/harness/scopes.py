"""Device time of the program's named scopes.

The program names its layers with ``jax.named_scope``: ``sparse``,
``blocks``, ``head`` and ``embed`` in the model, ``optimizer`` in the train
step, ``dst_grad``, ``dst_select`` and ``dst_apply`` in the DST update
(``COMMON``), and each architecture its own sublayers (its module's
``SCOPES`` in ``harness.archs``; qwen3's ``attention``). XLA keeps each
name in the ``op_name`` metadata of the instructions made from it, through
differentiation (``transpose(jvp(blocks))/.../sparse/dot_general``) and
remat (``.../checkpoint/rematted_computation/...``).

An operation of the trace is looked up by its instruction name in the
compiled program whose execution (the "XLA Modules" line) holds it, and its
device time goes to the innermost of that program's scopes (``names``)
its ``op_name`` names, or to ``other``: in the DST update the model's
scopes sit inside ``dst_grad`` and count as it. Scopes do not overlap, so
the scopes and ``other`` add up to the leaf operations' time in the
program's executions; the recompute (``rematted_computation``) is counted
besides, across them.

The compiled text is that of the train-step and DST programs built again
from the cell's files, as the training loop builds them (through the
configuration's architecture module), and lowered at
their shapes; with the persistent compilation cache on, compiling them
again loads the executable that ran. Time of an operation the text lacks
is reported (``unmatched``). A program without the scopes gives nothing.
"""
from __future__ import annotations

import bisect
import collections
import re

from harness import trace as TR
from harness.core import log

# program (found in module names) -> the scopes every architecture has
COMMON = {"train_step": ("sparse", "blocks", "head", "embed", "optimizer"),
          "dst_step": ("dst_grad", "dst_select", "dst_apply")}
OTHER = "other"
REMAT = "rematted_computation"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+) .*\{$")
_INSTR = re.compile(r'^\s+(?:ROOT\s+)?%?([^\s=]+) = ')
_OP_NAME = re.compile(r'(?<!\w)metadata=\{[^}]*?op_name="([^"]*)"')
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_CALL_SETS = re.compile(r"(?:branch|called)_computations=\{([^}]*)\}")
_NAME = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")


def names(model: dict) -> dict[str, tuple[str, ...]]:
    """Program -> its scopes, for a configuration's architecture."""
    from harness import archs
    own = archs.of(model).SCOPES
    return {p: s + own if p == "train_step" else s
            for p, s in COMMON.items()}


def instructions(hlo_text: str):
    """(computation, instruction name, the text after its " = ") of each
    instruction, with the lines it continues on: a Pallas call prints its
    ``kernel_metadata`` over three lines and its ``op_name`` on the third.
    A line that opens no computation or instruction and closes no
    computation continues the instruction before it."""
    comp, inst, rest = None, None, []
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        m = None if c else _INSTR.match(line)
        if inst is not None and (c or m or line.strip() in ("", "}")):
            yield comp, inst, "\n".join(rest)
            inst = None
        if c:
            comp = c.group(1)
        elif m:
            inst, rest = m.group(1), [line[m.end():]]
        elif inst is not None:
            rest.append(line)
    if inst is not None:
        yield comp, inst, "\n".join(rest)


def _opcode_and_operands(rest: str) -> tuple[str, list[str]]:
    """The opcode and operand names of an instruction, from the text after
    its " = "."""
    m = _OPCODE.search(rest)
    if not m:
        return "", []
    depth, i = 1, m.end()
    while i < len(rest) and depth:
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        i += 1
    return m.group(1), _NAME.findall(rest[m.end():i])


def op_paths(hlo_text: str) -> dict[str, tuple[str, str]]:
    """Instruction name -> (the ``op_name`` its time counts under, the rule
    that found it).

    An instruction's own ``op_name`` where it has one (``own``). XLA makes
    some without: a multi-output fusion takes the ``op_name`` most of the
    instructions fused into it carry (``fused``); any other takes that of
    the nearest instruction it reads (through its operands) that has one
    (``operand``), else that of the loop or call whose body holds it
    (``loop``); ("", ``none``) where none is found."""
    own, operands, fused, comp_of, caller = {}, {}, {}, {}, {}
    in_comp: dict[str, list[str]] = collections.defaultdict(list)
    for comp, inst, rest in instructions(hlo_text):
        name = _OP_NAME.search(rest)
        own[inst] = name.group(1) if name else ""
        opcode, operands[inst] = _opcode_and_operands(rest)
        comp_of[inst] = comp
        if name and comp is not None:
            in_comp[comp].append(name.group(1))
        called = _CALLS.findall(rest) + [
            n for group in _CALL_SETS.findall(rest)
            for n in re.findall(r"%?([\w.\-]+)", group)]
        for c in called:
            caller.setdefault(c, inst)
        if opcode == "fusion" and called:
            fused[inst] = called[0]

    def named(inst):
        if own.get(inst) or inst not in fused:
            return own.get(inst, ""), "own"
        inner = in_comp.get(fused[inst])
        return (collections.Counter(inner).most_common(1)[0][0]
                if inner else ""), "fused"

    out: dict[str, tuple[str, str]] = {}

    def resolve(inst):
        if inst in out:
            return out[inst]
        out[inst] = ("", "none")        # a cycle through callers finds none
        path, rule = named(inst)
        if not path:
            seen, queue = {inst}, collections.deque(operands.get(inst, ()))
            while queue and not path:
                o = queue.popleft()
                if o not in seen:
                    seen.add(o)
                    path, rule = named(o)[0], "operand"
                    queue.extend(operands.get(o, ()))
        if not path and comp_of.get(inst) in caller:
            path, rule = resolve(caller[comp_of[inst]])[0], "loop"
        out[inst] = (path, rule) if path else ("", "none")
        return out[inst]

    for inst in own:
        resolve(inst)
    return out


def _parts(path: str) -> list[str]:
    """The names of an ``op_name`` path, each unwrapped from the
    transformations around it ("transpose(jvp(blocks))" -> "blocks")."""
    parts = []
    for part in path.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        parts.append(part)
    return parts


def scope_of(path: str, scopes) -> str:
    """The innermost of ``scopes`` in an ``op_name`` path, else ``other``."""
    found = OTHER
    for part in _parts(path):
        if part in scopes:
            found = part
    return found


def attribute(events, modules, lo, hi,
              paths: dict[str, dict[str, tuple[str, str]]],
              scopes: dict[str, tuple[str, ...]]):
    """Per program (a key of ``paths`` and ``scopes``): its
    executions inside [lo, hi), their seconds, and the seconds of the leaf
    operations they hold by scope, with ``other``, the recompute
    (``remat``, across the scopes), the time of instructions the
    compiled text lacks (``unmatched``, inside ``other``) and each scope's
    seconds by the rule of ``op_paths`` that named them (``rules``)."""
    execs = sorted(clip_leaves(modules, lo, hi), key=lambda ev: ev[1])
    starts = [s for _, s, _ in execs]
    out = {p: {"executions": 0, "module_s": 0.0, "seconds": {},
               "remat": 0.0, "unmatched": 0.0, "other_ops": {},
               "rules": {}}
           for p in paths}

    def program(name):
        return next((p for p in paths if p in name), None)

    for name, s, e in execs:
        p = program(name)
        if p:
            out[p]["executions"] += 1
            out[p]["module_s"] += (e - s) / 1e9
    for name, s, e in clip_leaves(events, lo, hi):
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= execs[k][2]:
            continue                # outside every program execution
        p = program(execs[k][0])
        if p is None:
            continue
        r, dt = out[p], (e - s) / 1e9
        path, rule = paths[p].get(name, (None, "unmatched"))
        scope = OTHER if path is None else scope_of(path, scopes[p])
        r["seconds"][scope] = r["seconds"].get(scope, 0.0) + dt
        by_rule = r["rules"].setdefault(scope, {})
        by_rule[rule] = by_rule.get(rule, 0.0) + dt
        if path is None:
            r["unmatched"] += dt
        elif REMAT in _parts(path):
            r["remat"] += dt
        if scope == OTHER:
            key = path or name + (" (no op_name)" if path == ""
                                  else " (not in the compiled text)")
            r["other_ops"][key] = r["other_ops"].get(key, 0.0) + dt
    return out


def clip_leaves(events, lo, hi):
    return TR.clip(TR.leaves(events), lo, hi)


def compiled_texts(model: dict, traffic: dict) -> dict[str, str]:
    """The compiled text of the train-step and DST programs of a cell, built
    as the training loop builds them; empty where the program's ``Trainer``
    predates ``programs()`` (and the scopes)."""
    import jax
    import jax.numpy as jnp
    from repro.train.trainer import Trainer

    from harness import program, train
    from harness import weights as W
    cfg = program.arch_config(model, dtype=model["compute_dtype"],
                              param_dtype=model["param_dtype"])
    lr = float(model["optimizer"]["lr"])
    trainer = Trainer(cfg=cfg, lr_fn=lambda s: jnp.float32(lr), log_every=1)
    if not hasattr(trainer, "programs"):
        return {}
    rows, seq = int(traffic["batch"]), int(traffic["seq_len"])
    params, masks = jax.eval_shape(
        lambda: W.make(model, model["param_dtype"], 0))
    state = jax.eval_shape(
        lambda p, m: train._state(cfg, trainer.registry, p, m, 0,
                                  jax.random.PRNGKey(0)), params, masks)
    batch = jax.eval_shape(lambda: train._batch(
        jax.random.PRNGKey(0), 0, rows, seq, model["vocab_size"]))
    return {name: fn.lower(state, batch).compile().as_text()
            for name, fn in zip(COMMON, trainer.programs()) if fn}


def reading(out) -> dict | None:
    """The attribution of the traced window of a training run on device 0,
    made once and kept in ``out.counters``; None without a trace or where
    the program predates the scopes. A program that has them but cannot be
    built again fails the run. Logs every scope's time, the rules that
    named it and what ``other`` holds."""
    if "scopes" in out.counters:
        return out.counters["scopes"]
    t, got = out.trace, None
    if t is not None and t.modules and t.modules[0]:
        texts = compiled_texts(out.model, out.traffic)
        if texts:
            got = attribute(t.devices[0], t.modules[0], t.lo, t.hi,
                            {p: op_paths(x) for p, x in texts.items()},
                            names(out.model))
            _log(got)
    out.counters["scopes"] = got
    return got


def _log(got: dict) -> None:
    for p, r in got.items():
        n = r["executions"]
        if not n:
            continue
        ms = lambda s: f"{s / n * 1e3:.3f}"
        leaf = sum(r["seconds"].values())
        parts = ", ".join(f"{k} {ms(v)}" for k, v in sorted(
            r["seconds"].items(), key=lambda kv: -kv[1]))
        log(f"[scopes] {p}: {n} execution(s), ms per execution: module "
            f"{ms(r['module_s'])}, leaf operations {ms(leaf)} = {parts}; "
            f"recompute {ms(r['remat'])}; not in the compiled text "
            f"{ms(r['unmatched'])}")
        for scope, by_rule in sorted(r["rules"].items()):
            log(f"[scopes]   {scope} by rule: " + ", ".join(
                f"{k} {ms(v)}" for k, v in sorted(by_rule.items())))
        top = sorted(r["other_ops"].items(), key=lambda kv: -kv[1])[:8]
        for path, s in top:
            log(f"[scopes]   other {ms(s)} ms: {path}")


def ms_per_execution(out, program: str, scope: str) -> float | None:
    """Device ms of ``scope`` (or ``"remat"``) per execution of
    ``program`` in the traced window; None where nothing was attributed
    to it."""
    got = reading(out)
    r = (got or {}).get(program)
    if not r or not r["executions"]:
        return None
    secs = r["remat"] if scope == "remat" else r["seconds"].get(scope, 0.0)
    return secs / r["executions"] * 1e3 if secs > 0 else None
