"""Profiler trace (``.xplane.pb``) -> device busy time, per-op seconds, gaps.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.  On a
TPU every chip is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per executed operation (start and duration in
nanoseconds), ``XLA Modules`` one per executed program.

busy      the union of the ``XLA Ops`` intervals of a chip, averaged over
          the chips that ran anything.
window    first op start to last op end over all chips: the part of the
          profiled span in which the device was observed.  Idle time before
          the first and after the last op of the profile is not counted;
          it is at most one step or one gap.
gaps      the longest idle intervals between two ops, each labelled by the
          op that ended it (the host spans that would say what the host
          was doing are not on the profiler's clock yet).
"""

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _intervals(line):
    out = []
    for ev in line.events:
        start = float(ev.start_ns)
        out.append((start, start + float(ev.duration_ns), ev.name))
    out.sort()
    return out


def union_and_gaps(intervals):
    """``intervals``: sorted (start, end, name).  Returns (busy, gaps) with
    gaps as (length, name of the op that ended the gap)."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e, name in intervals:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            busy += cur_e - cur_s
            gaps.append((s - cur_e, name))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def op_name(name):
    """An event is named by its op's whole HLO text; ``%fusion.123 = f32[..``
    -> ``fusion.123``."""
    return name.split(" = ")[0].lstrip("%")


def short_name(name):
    """``fusion.123`` -> ``fusion``: XLA numbers its ops, and a breakdown
    by kind has to add the numbered ones up."""
    name = op_name(name)
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def reduce_planes(planes, top=10):
    """``planes``: iterable of (plane name, {line name: sorted intervals}).
    Times in the result are seconds."""
    chips = []
    per_op = defaultdict(float)
    per_module = defaultdict(lambda: [0.0, 0])
    all_gaps = []
    first, last, n_ops = None, None, 0
    for name, lines in planes:
        if not name.startswith(DEVICE_PREFIX) or not lines.get(OPS_LINE):
            continue
        ops = lines[OPS_LINE]
        busy, gaps = union_and_gaps(ops)
        chips.append(busy)
        all_gaps += gaps
        n_ops += len(ops)
        for s, e, op in ops:
            per_op[short_name(op)] += e - s
        for s, e, mod in lines.get(MODULES_LINE, ()):
            per_module[mod.split("(")[0]][0] += e - s
            per_module[mod.split("(")[0]][1] += 1
        first = ops[0][0] if first is None else min(first, ops[0][0])
        end = max(e for _, e, _ in ops)
        last = end if last is None else max(last, end)
    if not chips:
        return None
    gap_by_op = defaultdict(float)
    for length, op in all_gaps:
        gap_by_op[short_name(op)] += length
    ns = 1e-9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "chips": len(chips),
        "busy_s": sum(chips) / len(chips) * ns,
        "window_s": (last - first) * ns,
        "n_ops": n_ops,
        "device_ops": [[k, v * ns] for k, v in rank(per_op)],
        "idle_gaps": [[f"before {op_name(op)}", length * ns] for length, op in
                      sorted(all_gaps, key=lambda g: -g[0])[:top]],
        "idle_by_next_op": [[f"before {k}", v * ns / len(chips)]
                            for k, v in rank(gap_by_op)],
        "modules": [[k, v[0] * ns, v[1]] for k, v in
                    sorted(per_module.items(), key=lambda kv: -kv[1][0])[:top]],
    }


def reduce_file(path, top=10):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = [(p.name, {l.name: _intervals(l) for l in p.lines
                        if l.name in (OPS_LINE, MODULES_LINE)})
              for p in data.planes]
    return reduce_planes(planes, top)


def reduce_dir(trace_dir, top=10):
    path = find_xplane(trace_dir)
    return reduce_file(path, top) if path else None
