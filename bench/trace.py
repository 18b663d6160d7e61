"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time as the union of operation intervals, device
time per kernel, host spans, and the device's idle gaps labelled by the
host span that was open during each.

The reduction works on plain ``(start_ns, end_ns, name, kernel)``
intervals, so a test can check it on intervals counted by hand.  ``load``
fills them from the trace: device operations from each ``/device:TPU:<i>``
plane's ``XLA Ops`` line (nested operations, such as a loop and its body,
count once in the union), host spans from the host plane's threads.

An operation's kernel is the ``jax.named_scope`` around its
``pallas_call``, read from the op_name in the operation's event metadata
(``jit(step)/.../vmap(fsa_selected_dq)/pallas_call`` -> ``fsa_selected_dq``),
so a kernel keeps its name however XLA names or outlines the call; '' for
an operation that is no kernel.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re


@dataclasses.dataclass
class Trace:
    devices: dict          # device plane -> [(start, end, op name, kernel)]
    host: list             # [(start, end, name)] on the host's threads


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    raw = open(files[-1], "rb").read()
    kernels = event_kernels(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    devices, host = {}, []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            names = kernels.get(plane.name, {})
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                             names.get(e.name, "")) for e in line.events]
            devices[plane.name] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events]
    return Trace(devices, host)


# The event metadata, which ``jax.profiler.ProfileData`` does not expose,
# read from the file with a minimal protobuf decoder.  Field numbers:
# XSpace.planes 1; XPlane: name 2, event_metadata 4 (map entries: key 1,
# value 2), stat_metadata 5; XEventMetadata: name 2, display_name 4,
# stats 5; XStatMetadata: id 1, name 2; XStat: str_value 5, ref_value 7
# (the id of a stat_metadata entry whose name is the string).

def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def fields(b):
    """(field number, value) of a protobuf message's fields: an int for a
    varint, a memoryview for anything else."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def event_kernels(raw: bytes) -> dict:
    """{plane name: {event name: kernel}} for the events whose metadata
    holds an op_name with a kernel's scope."""
    out = {}
    for f, plane in fields(raw):
        if f != 1:
            continue
        name, metas, strings = "", [], {}
        for g, v in fields(plane):
            if g == 2:
                name = _text(v)
            elif g in (4, 5):
                entry = dict(fields(v)).get(2)
                if entry is None:
                    continue
                if g == 4:
                    metas.append(entry)
                else:
                    sm = dict(fields(entry))
                    strings[sm.get(1, 0)] = _text(sm.get(2, b""))
        table = out.setdefault(name, {})
        for m in metas:
            names, kernel = [], ""
            for g, v in fields(m):
                if g in (2, 4):
                    names.append(_text(v))
                elif g == 5 and not kernel:
                    st = dict(fields(v))
                    text = (_text(st[5]) if 5 in st
                            else strings.get(st.get(7), ""))
                    kernel = scope_kernel(text)
            if kernel:
                table.update({n: kernel for n in names if n})
    return out


def union(intervals) -> list:
    """Merge (start, end, ...) intervals; returns sorted [(start, end)]."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0, t1) -> list:
    return [(max(s, t0), min(e, t1), *rest) for s, e, *rest in intervals
            if e > t0 and s < t1]


def busy_ns(ops, t0, t1) -> float:
    return float(sum(e - s for s, e in union(clip(ops, t0, t1))))


PALLAS = re.compile(r"([^/\s\"]+)/pallas_call\b")


def scope_kernel(op_name: str) -> str:
    """The named scope around a ``pallas_call`` in an op_name path, with
    JAX's transformation wrappers taken off: '.../checkpoint/vmap(
    fsa_selected_dkv)/pallas_call' -> 'fsa_selected_dkv'; '' if the path
    holds no ``pallas_call``."""
    m = PALLAS.search(op_name)
    if not m:
        return ""
    name = m.group(1)
    while (w := re.fullmatch(r"[\w.]+\((.+)\)", name)):
        name = w.group(1)
    return name


def kernel_ns(ops, kernel: str, t0, t1) -> tuple[float, int]:
    """(device ns, calls) of ``kernel``'s operations inside [t0, t1)."""
    hits = [(s, e) for s, e, _, k in clip(ops, t0, t1) if k == kernel]
    return float(sum(e - s for s, e in hits)), len(hits)


def scoped_kernels(ops) -> dict:
    """{kernel: operations} of the kernels' operations."""
    return dict(collections.Counter(k for *_, k in ops if k))


def spans(host, name: str, t0=None, t1=None) -> list:
    """Host spans called ``name`` (starting inside [t0, t1) if given)."""
    return [(s, e) for s, e, n in host if n == name
            and (t0 is None or t0 <= s < t1)]


def self_ns(parent, children) -> float:
    """Time of ``parent`` not covered by any of ``children``."""
    s0, e0 = parent
    return (e0 - s0) - sum(e - s for s, e in union(clip(children, s0, e0)))


def idle_gaps(ops, host, t0, t1, labels, top: int = 10) -> list:
    """The longest device-idle gaps in [t0, t1), each labelled with the
    innermost host span among ``labels`` open at its midpoint ('none' if
    no such span).  Returns [[label, seconds], ...], summed per label for
    gaps of one label and sorted longest first."""
    busy = union(clip(ops, t0, t1))
    gaps, prev = [], t0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if prev < t1:
        gaps.append((prev, t1))
    open_spans = [(s, e, n) for s, e, n in host if n in labels]
    per = collections.Counter()
    for s, e in gaps:
        mid = (s + e) / 2
        inner = [(ss, ee, n) for ss, ee, n in open_spans if ss <= mid < ee]
        label = min(inner, key=lambda x: x[1] - x[0])[2] if inner else "none"
        per[label] += (e - s) / 1e9
    return [[k, v] for k, v in per.most_common(top)]


def top_ops(ops, t0, t1, top: int = 10) -> list:
    """Device operations that took the most time: kernels by name, other
    operations by HLO instruction (summed over a loop's iterations).  A
    loop that contains others is not counted itself."""
    per = collections.Counter()
    for s, e, n, k in clip(ops, t0, t1):
        head = n.split(" = ", 1)[0].lstrip("%")
        if head.startswith("while"):
            continue
        per[k or head] += (e - s) / 1e9
    return [[k, v] for k, v in per.most_common(top)]
