"""The program's layer scopes and span arguments in a traced run.

The program labels the layers inside its step and tick programs with
``jax.named_scope`` names (``repro.telemetry.SCOPES``); each device
operation of the trace carries them in its op_name.  An operation belongs
to the innermost name of :data:`NAMES` in its path, after JAX's wrappers
are taken off (``vmap(nsa.index)`` -> ``nsa.index``, ``transpose(jvp(
mlp))`` -> ``mlp``), or to its kernel where it is a Pallas kernel (the
scope just before ``pallas_call``, as ``bench.trace`` names it).  Its
sub-step is the innermost ``tick.*`` name in the path (the mixed tick runs
its decode sub-step under ``tick.decode`` inside ``tick.prefill``), and it
is a recompute where the path holds ``rematted_computation``.  Where XLA
dropped an operation's op_name, it is recovered from the compiled program
the trace keeps (``program_op_names``).

The engine's spans carry the counts each tick dispatched as arguments of
their profiler annotation (``rows``, ``live_rows``, ``decode_rows``); they
are read from the host plane, on the clock of the device operations.

Everything here reads the traced run's own ``.xplane.pb``: the newest under
``.bench_trace/<cell>-<seed>/``, the directory ``bench/run.py`` reduces.  A
program without the scopes or the span arguments reads as nothing: the
metrics that read them return None.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import sys

from bench import trace as tr

# The scope names this benchmark reads; tests check them against the
# program's list.
NAMES = ("tick.prefill", "tick.decode", "embed", "attn.qkv", "nsa.compress",
         "nsa.select", "nsa.index", "nsa.window", "nsa.gate", "attn.out",
         "kv.gather", "kv.write", "mlp", "lm_head", "optimizer")
TICKS = ("tick.prefill", "tick.decode")
REMAT = "rematted_computation"
OP_NAME_STAT = "tf_op"      # the event-metadata stat that holds the op_name


@dataclasses.dataclass(frozen=True)
class Attr:
    scope: str          # innermost name of NAMES, or the kernel; '' if none
    tick: str           # innermost tick.* name; '' if none
    remat: bool         # under rematted_computation
    path: tuple         # the names of NAMES in the path, outermost first


def components(op_name: str) -> list:
    """The path's components with JAX's transformation wrappers taken off:
    'jit(f)/transpose(jvp(mlp))/vmap()/dot' -> ['f', 'mlp', '', 'dot']."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(op_name + "/"):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    unwrapped = []
    for c in out:
        while (m := re.fullmatch(r"[\w.]+\((.*)\)", c)):
            c = m.group(1)
        unwrapped.append(c)
    return unwrapped


def attribute(op_name: str) -> Attr:
    comps = components(op_name)
    path = tuple(c for c in comps if c in NAMES)
    kernel = tr.scope_kernel(op_name)
    scope = kernel or (path[-1] if path else "")
    tick = next((c for c in reversed(path) if c in TICKS), "")
    return Attr(scope, tick, REMAT in comps, path)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> list:
    """A repeated int64 field's values: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = tr._varint(v, i)
        out.append(x)
    return out


# The compiled programs, which the trace keeps as HloProto bytes in the
# event metadata of its ``/host:metadata`` plane (one entry a program, its
# id the program's).  Field numbers: HloProto.hlo_module 1; HloModuleProto
# computations 3; HloComputationProto: instructions 2, id 5, root_id 6;
# HloInstructionProto: name 1, opcode 2, metadata 7, id 35, operand_ids
# 36, called_computation_ids 38; OpMetadata.op_name 2.

def dropped(op_name: str) -> bool:
    """An op_name XLA left empty, or stamped with the loop or branch an
    operation was created or moved for (``jit(step)/jvp()/while``)."""
    return components(op_name.rstrip(":"))[-1] in ("", "while", "cond")


def program_op_names(hlo: bytes) -> dict:
    """{instruction name: op_name} of one compiled program.  An instruction
    whose op_name XLA dropped (``dropped``: some fusions, copies and sorts,
    in loop bodies above all) takes its fused computation's (the root's,
    else the last instruction's that has one), else its first operand's
    that has one (a parameter's is only its argument's name), else the
    loop's op_name it or an operand was stamped with (the scopes around
    that loop), else the components that every op_name of its own
    computation shares."""
    comps = {}
    for f, module in tr.fields(hlo):
        if f != 1:
            continue
        for g, comp in tr.fields(module):
            if g != 3:
                continue
            cid, root, insts = 0, None, []
            for h, v in tr.fields(comp):
                if h == 5:
                    cid = v
                elif h == 6:
                    root = v
                elif h == 2:
                    ins = {"called": [], "operands": [], "op": "",
                           "stamp": ""}
                    for k, w in tr.fields(v):
                        if k == 1:
                            ins["name"] = _text(w)
                        elif k == 7:
                            op = _text(dict(tr.fields(w)).get(2, b""))
                            ins["stamp" if dropped(op) else "op"] = op
                        elif k == 35:
                            ins["id"] = w
                        elif k == 36:
                            ins["operands"] += _ints(w)
                        elif k == 38:
                            ins["called"] += _ints(w)
                    insts.append(ins)
            comps[cid] = (root, insts)

    def common(insts) -> str:
        """The op_name components every named instruction shares."""
        paths = [i["op"].split("/") for i in insts if "/" in i["op"]]
        if not paths:
            return ""
        n = 0
        while all(len(p) > n + 1 and p[n] == paths[0][n] for p in paths):
            n += 1
        return "/".join(paths[0][:n] + ["?"]) if n else ""

    def inner(cid, depth=0) -> str:
        root, insts = comps.get(cid, (None, []))
        named = [i for i in insts if i["op"]]
        for i in named:
            if i.get("id") == root:
                return i["op"]
        if named:
            return named[-1]["op"]
        for i in reversed(insts):
            for c in i["called"]:
                if depth < 4 and (op := inner(c, depth + 1)):
                    return op
        return ""

    out = {}
    for _, insts in comps.values():
        by_id = {i.get("id"): i for i in insts}
        shared = None
        for i in insts:
            operands = [by_id.get(x, {}) for x in i["operands"]]
            op = i["op"] or next(
                (o for c in i["called"] if (o := inner(c))), "") or next(
                (o for x in operands if "/" in (o := x.get("op", ""))),
                "") or next(
                (o for x in [i] + operands if (o := x.get("stamp"))), "")
            if not op:
                shared = common(insts) if shared is None else shared
                op = shared
            if op and "name" in i:
                out[i["name"]] = op
    return out


def programs(raw: bytes) -> dict:
    """{program id: {instruction name: op_name}} from ``/host:metadata``."""
    out = {}
    for f, plane in tr.fields(raw):
        if f != 1:
            continue
        fields = list(tr.fields(plane))
        if not any(g == 2 and _text(v) == "/host:metadata"
                   for g, v in fields):
            continue
        for g, v in fields:
            if g != 4:
                continue
            meta = dict(tr.fields(v)).get(2)
            if meta is None:
                continue
            pid, hlo = None, None
            for h, w in tr.fields(meta):
                if h == 1:
                    pid = w
                elif h == 5:
                    hlo = dict(tr.fields(w)).get(6, hlo)
            if pid is not None and hlo is not None:
                out[pid] = program_op_names(hlo)
    return out


def op_names(raw: bytes) -> dict:
    """{device plane: {event name: op_name}}: the ``tf_op`` stat of the
    event metadata; where XLA dropped it, the compiled program's op_name
    for the instruction (``program_op_names``); else the first string stat
    that reads as a path."""
    progs = programs(raw)
    out = {}
    for f, plane in tr.fields(raw):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in tr.fields(plane):
            if g == 2:
                name = _text(v)
            elif g in (4, 5):
                entry = dict(tr.fields(v)).get(2)
                if entry is None:
                    continue
                if g == 4:
                    metas.append(entry)
                else:
                    sm = dict(tr.fields(entry))
                    stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        if not name.startswith("/device:"):
            continue
        table = out.setdefault(name, {})
        for m in metas:
            names, found, fallback, pid = [], "", "", None
            for g, v in tr.fields(m):
                if g in (2, 4):
                    names.append(_text(v))
                elif g == 5:
                    st = dict(tr.fields(v))
                    stat = stat_names.get(st.get(1))
                    if stat == "program_id":
                        pid = st.get(3, st.get(4))
                        continue
                    text = (_text(st[5]) if 5 in st
                            else stat_names.get(st.get(7), ""))
                    if stat == OP_NAME_STAT:
                        found = text
                    elif not fallback and re.match(r"[\w.]+\([^/]*\)/", text):
                        fallback = text
            if dropped(found) and pid in progs:
                hlo = progs[pid]
                found = next((hlo[h] for n in names
                              if (h := re.sub(r"^(ROOT )?%", "",
                                              n.split(" = ", 1)[0])) in hlo),
                             found)
            op = found or fallback
            if op:
                table.update({n: op for n in names if n})
    return out


@dataclasses.dataclass
class Scoped:
    ops: list        # [(start, end, Attr, event name, op_name)] leaf
    #                  device ops, clipped to the window
    spans: list      # [(start, end, name, {arg: value})] host spans
    t0: float
    t1: float
    named: int = 0   # leaf ops whose op_name the metadata gave

    def ns(self, keep, t0=None, t1=None) -> float:
        """Device ns of the operations ``keep(attr)`` accepts that start
        inside [t0, t1) (the window if not given)."""
        t0 = self.t0 if t0 is None else t0
        t1 = self.t1 if t1 is None else t1
        return float(sum(e - s for s, e, a, *_ in self.ops
                         if t0 <= s < t1 and keep(a)))

    def host(self, name: str) -> list:
        """Spans called ``name`` that start inside the window."""
        return [sp for sp in self.spans
                if sp[2] == name and self.t0 <= sp[0] < self.t1]

    def ticks(self) -> list:
        """The ``engine.tick`` spans inside the window that dispatched a
        tick program, each as (start, end, mixed)."""
        work = self.host("engine.prefill_chunk") + self.host("engine.decode")
        out = []
        for s, e, _, _ in self.host("engine.tick"):
            if e > self.t1:
                continue
            inner = [n for ws, _, n, _ in work if s <= ws < e]
            if inner:
                out.append((s, e, "engine.prefill_chunk" in inner))
        return out


LOOP = re.compile(r"(ROOT )?%?(while|conditional|call)(\.[\w.]*)?( = .*)?")


def leaves(ops) -> list:
    """The operations that run work themselves: a loop's (or branch's,
    or call's) body counts, the loop's own event, which spans it, does
    not."""
    return [o for o in ops if not LOOP.fullmatch(o[2])]


def trace_file(cell: str) -> str:
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".bench_trace")
    dirs = [d for d in glob.glob(os.path.join(root, f"{glob.escape(cell)}-*"))
            if re.fullmatch(r"-?\d+", os.path.basename(d)[len(cell) + 1:])]
    files = [f for d in dirs for f in glob.glob(
        os.path.join(d, "**", "*.xplane.pb"), recursive=True)]
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}/{cell}-<seed>")
    return max(files, key=os.path.getmtime)


def host_spans(raw: bytes) -> list:
    """The engine's spans on the host plane, each with its arguments."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("engine.")]
    return out


def build(raw: bytes, plane_ops: dict, t0: float, t1: float) -> Scoped:
    """``plane_ops``: {device plane: [(start, end, op name, kernel)]} as
    ``bench.trace.load`` reads them; the first plane is attributed."""
    plane, ops = next(iter(plane_ops.items()))
    names = op_names(raw).get(plane, {})
    leaf = leaves(tr.clip(ops, t0, t1))
    scoped = [(s, e, attribute(names.get(n, "")), n, names.get(n, ""))
              for s, e, n, _ in leaf]
    return Scoped(scoped, host_spans(raw), t0, t1,
                  sum(1 for *_, n, _ in leaf if n in names))


def of(run) -> Scoped:
    """The run's scoped trace, read once per run (and its summary logged)."""
    got = getattr(run, "scoped", None)
    if got is None:
        with open(trace_file(run.cell.name), "rb") as f:
            raw = f.read()
        got = run.scoped = build(raw, run.trace.devices, run.t0, run.t1)
        log_summary(got)
    return got


def summary(sc: Scoped, top: int = 8) -> dict:
    """Device seconds per scope, the unclaimed share of the leaf operations'
    time, the largest unclaimed operations, and the sub-steps' share of the
    device time inside the dispatching ticks."""
    per = collections.Counter()
    unclaimed = collections.Counter()
    for s, e, a, n, op in sc.ops:
        per[a.scope or "(none)"] += (e - s) / 1e9
        if not a.scope:
            unclaimed[f"{n.split(' = ', 1)[0].lstrip('%')} {op[-90:]}"] += \
                (e - s) / 1e9
    total = sum(per.values())
    out = {"device_s": total,
           "unclaimed_share": per["(none)"] / total if total else None,
           "per_scope": dict(per.most_common()),
           "unclaimed_top": unclaimed.most_common(top)}
    ticks = sc.ticks()
    if ticks:
        in_ticks = sum(sc.ns(lambda a: True, s, e) for s, e, _ in ticks)
        sub = sum(sc.ns(lambda a: bool(a.tick), s, e) for s, e, _ in ticks)
        out["tick_substep_share"] = sub / in_ticks if in_ticks else None
    return out


def log_summary(sc: Scoped) -> None:
    s = summary(sc)
    share = s["unclaimed_share"]
    print(f"[scopes] {len(sc.ops)} leaf device ops in the window, "
          f"{sc.named} with an op_name", file=sys.stderr, flush=True)
    print(f"[scopes] unclaimed share of device time: "
          f"{'n/a' if share is None else f'{100 * share:.2f}%'} of "
          f"{s['device_s']:.4f} s; largest unclaimed: "
          f"{[[n, round(v, 6)] for n, v in s['unclaimed_top']]}",
          file=sys.stderr, flush=True)
    print(f"[scopes] device s per scope: "
          f"{ {k: round(v, 6) for k, v in s['per_scope'].items()} }",
          file=sys.stderr, flush=True)
    if "tick_substep_share" in s:
        print(f"[scopes] tick.prefill + tick.decode share of device time in "
              f"dispatching engine.tick spans: {s['tick_substep_share']}",
              file=sys.stderr, flush=True)


def per_step(run, keep):
    """Device ms per traced training step of the operations ``keep``
    accepts; None if none ran."""
    ns = of(run).ns(keep)
    steps = run.result.get("traced_steps", 0)
    return ns / 1e6 / steps if ns > 0 and steps else None


def per_tick(run, keep, mixed_only: bool = False):
    """Device ms per dispatching engine tick (per mixed tick with
    ``mixed_only``) of the operations ``keep`` accepts that start inside
    it; None if none ran."""
    sc = of(run)
    ticks = [t for t in sc.ticks() if t[2] or not mixed_only]
    ns = sum(sc.ns(keep, s, e) for s, e, _ in ticks)
    return ns / 1e6 / len(ticks) if ns > 0 else None
