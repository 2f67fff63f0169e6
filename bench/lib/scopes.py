"""Device time of the federated round by sub-layer, read from the op
names that the program's named scopes leave in the trace.

The device planes of a profiler trace carry, in the metadata of each
op event, the op's ``tf_op`` stat: the JAX name stack the op was traced
under, scopes included (``jit(fn)/while/body/.../transpose(jvp(
fed.model))/dot_general:``).  ``jax.profiler.ProfileData`` does not
expose event-metadata stats, so ``op_paths`` decodes them straight from
the protobuf wire format of the ``.xplane.pb`` file.

``attribute`` gives each op event of the window one owner:

- an event whose name matches a kernel reader's ``PATTERN``
  (``reconstruct_roofline``, ``bwd_plan_roofline``) belongs to that
  kernel;
- any other event belongs to the innermost program scope (``SCOPES``)
  named in its path, which may sit inside ``jvp(...)`` or
  ``transpose(...)`` wrappers;
- the rest is ``UNSCOPED``.
"""

from __future__ import annotations

import re
import sys
import time
from typing import Dict, Iterator, Optional, Tuple

from bench.lib.trace import DEVICE_PLANE, short_name

# the program's scopes (``repro.tracing.SCOPES``), named here so that a
# scope renamed in the program reads as nothing rather than moving
SCOPES = ("fed.model", "fed.update", "fed.upload", "fed.aggregate",
          "fed.downlink", "qz.layout")
KERNEL_READERS = ("reconstruct_roofline", "bwd_plan_roofline")
UNSCOPED = "unscoped"
TF_OP = "tf_op"
PROFILE_START = "profile_start_time"

_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(map(re.escape, SCOPES))
                    + r")(?![\w.])")


# --- the wire format --------------------------------------------------------
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, the
    raw bytes for every other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _map_values(entries):
    """Values of a protobuf map's entries (key 1, value 2)."""
    for entry in entries:
        yield dict(_fields(entry)).get(2, b"")


def _planes(path: str):
    """(name, fields, {stat metadata id: stat name}) of each plane of an
    ``.xplane.pb`` file.  Fields: XSpace.planes 1; XPlane.name 2,
    event_metadata 4, stat_metadata 5, stats 6; XStatMetadata.name 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for n, v in fields if n == 2), "")
        stat_names = {}
        for md in _map_values(v for n, v in fields if n == 5):
            md = dict(_fields(md))
            stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        yield name, fields, stat_names


def op_paths(path: str) -> Dict[str, str]:
    """{op event name: its ``tf_op`` path} over the device planes.
    Fields: XEventMetadata.name 2, stats 5; XStat.metadata_id 1, str 5,
    bytes 6, ref 7 (the id of a stat metadata that holds the string)."""
    out: Dict[str, str] = {}
    for name, fields, stat_names in _planes(path):
        if not DEVICE_PLANE.match(name):
            continue
        for md in _map_values(v for n, v in fields if n == 4):
            md = list(_fields(md))
            ev = next((bytes(v).decode() for n, v in md if n == 2), "")
            for stat in (dict(_fields(v)) for n, v in md if n == 5):
                if stat_names.get(stat.get(1)) != TF_OP:
                    continue
                if 7 in stat:
                    val = stat_names.get(stat[7], "")
                else:
                    val = bytes(stat.get(5, stat.get(6, b""))).decode()
                out[ev] = val
    return out


def profile_start_ns(path: str) -> Optional[int]:
    """The wall-clock time (ns since the epoch) at which the profile
    started: the origin of every event time ``ProfileData`` gives, and
    so of ``Trace.lo`` / ``Trace.hi``.  XStat.int64 4, uint64 3."""
    for name, fields, stat_names in _planes(path):
        for n, v in fields:
            if n != 6:
                continue
            stat = dict(_fields(v))
            if stat_names.get(stat.get(1)) == PROFILE_START:
                return stat.get(4, stat.get(3))
    return None


def window_file() -> Optional[str]:
    """The trace of the window just run: the file ``read_trace`` read."""
    from bench.run import TRACE_DIR

    files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
    return str(files[-1]) if files else None


# --- attribution -------------------------------------------------------------
def scope_of(path: str) -> Optional[str]:
    """The innermost program scope named in a ``tf_op`` path."""
    found = _SCOPE.findall(path)
    return found[-1] if found else None


def _owners(trace, paths: Dict[str, str], kernels: Dict[str, str]):
    """(owner, seconds, event name) of each op event of the window."""
    rx = {k: re.compile(p) for k, p in kernels.items()}
    for chip in trace.chips:
        for s, e, name in trace.ops[chip]:
            owner = next((k for k, r in rx.items() if r.search(name)), None)
            if owner is None:
                owner = scope_of(paths.get(name, "")) or UNSCOPED
            yield owner, (e - s) * 1e-9, name


def attribute(trace, paths: Dict[str, str],
              kernels: Dict[str, str]) -> Dict[str, Tuple[float, int]]:
    """{owner: (summed device seconds over all chips, event count)} of
    the window's op events; owners are the ``kernels`` ({name: event
    name pattern}), the ``SCOPES`` and ``UNSCOPED``."""
    secs = dict.fromkeys([*kernels, *SCOPES, UNSCOPED], 0.0)
    count = dict.fromkeys(secs, 0)
    for owner, dt, _ in _owners(trace, paths, kernels):
        secs[owner] += dt
        count[owner] += 1
    return {k: (secs[k], count[k]) for k in secs}


def top_unscoped(trace, paths: Dict[str, str], kernels: Dict[str, str],
                 k: int = 5):
    """The ``k`` unscoped ops that take longest: [short name, path (empty
    for an op the compiler inserted, which carries no op_name), summed
    seconds over all chips]."""
    agg: Dict[str, float] = {}
    for owner, dt, name in _owners(trace, paths, kernels):
        if owner == UNSCOPED:
            agg[name] = agg.get(name, 0.0) + dt
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[short_name(n), paths.get(n, ""), v] for n, v in top]


def kernel_patterns() -> Dict[str, str]:
    """The kernel readers' own ``PATTERN``s."""
    from bench.run import BENCH, load_module

    return {k: load_module(BENCH / "metrics" / (k + ".py"),
                           "bench_metric_" + k).PATTERN
            for k in KERNEL_READERS}


def window_owners(ctx) -> Dict[str, Tuple[float, int]]:
    """``attribute`` over the traced window (the file ``read_trace``
    read), computed once per window."""
    if "scope_owners" not in ctx:
        t0 = time.perf_counter()
        path = window_file()
        paths = op_paths(path) if path else {}
        kernels = kernel_patterns()
        ctx["scope_owners"] = attribute(ctx["trace"], paths, kernels)
        dt = time.perf_counter() - t0
        w = ctx["window"]
        rounds = max(w["units"], 1)
        owned = ctx["scope_owners"]
        t = ctx["trace"]
        busy = t.busy_s() * len(t.chips)
        total = sum(secs for secs, _ in owned.values())
        print(f"bench: traced window {w['units']} rounds, "
              f"{w['elapsed_s'] * 1e3 / rounds:.3f} ms a round; op paths "
              f"of {len(paths)} device ops decoded and attributed in "
              f"{dt:.3f} s; owners sum to {total / max(busy, 1e-12):.6f} "
              f"of busy time; ms a round: " + ", ".join(
                  f"{k} {v[0] * 1e3 / rounds:.4f}" for k, v in owned.items()),
              file=sys.stderr)
        for name, op, secs in top_unscoped(ctx["trace"], paths, kernels):
            print(f"bench: unscoped {name} {secs * 1e3 / rounds:.4f} ms a "
                  f"round ({op or 'no op_name'})", file=sys.stderr)
    return ctx["scope_owners"]


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device ms per round under ``scope``, mean over the chips; nothing
    when no op event of the window carries the scope."""
    secs, n = window_owners(ctx)[scope]
    rounds, chips = ctx["window"]["units"], len(ctx["trace"].chips)
    if n == 0 or rounds <= 0:
        return None
    return secs * 1e3 / chips / rounds


def unscoped_share(ctx) -> Optional[float]:
    """Percent of device busy time in op events that are neither a
    kernel's nor under a program scope."""
    t = ctx["trace"]
    busy = t.busy_s() * len(t.chips)
    if busy <= 0:
        return None
    return 100.0 * window_owners(ctx)[UNSCOPED][0] / busy
