"""Device milliseconds a run by ``jax.named_scope`` for the busiest programs of
one profiler trace (PERF.md section 5's per-scope tables).

    python3 scripts/trace_scope_table.py <file.xplane.pb[.gz]>

The scope is the ``tf_op`` stat of an operation event's *metadata*, which
``jax.profiler.ProfileData`` (what ``benchmark/trace.py`` reads) does not hand
out: this reads the raw protobuf through tensorflow's ``xplane_pb2``. The file
is any ``jax.profiler`` capture (``<log_dir>/plugins/profile/*/*.xplane.pb``).
A benchmark run removes its own (``.benchmark_work/<cell>/trace``) when it
ends, so copy that one out before ``run_cell`` returns. A fusion carries the
scope of one of its operations, so neighbouring scopes are not to be read
finely. A ``while`` loop's or a ``conditional``'s own event spans the events
of its body, which carry their scopes: it is listed apart and is in no
scope's sum (summed in, a program's experts behind conditionals read twice
what they cost: PERF.md section 6, PR 37).
"""
import collections
import gzip
import os
import re
import sys

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

#: the scopes the programs set (models/transformer.py, generate.py,
#: training/train_step.py), the longer name before its prefix
SCOPES = ("cache_update", "window_attention", "linear_attention",
          "selective_scan", "ssm_conv", "ssm_proj", "linear_conv", "linear_gates", "linear_proj", "attention_gate",
          "attention", "moe_router",
          "moe_experts", "moe_shared", "mlp", "head_xent", "head",
          "cross_entropy", "sampling")
PROGRAMS_SHOWN = 3
#: operations whose event spans their body's events
CONTAINERS = ("while", "conditional")


def union_ps(intervals) -> int:
    total, end = 0, 0
    for a, b in sorted(intervals):
        total += max(b, end) - max(a, end)
        end = max(b, end)
    return total


def scope_of(tf_op: str) -> str:
    for scope in SCOPES:
        if re.search(r"[/(]" + scope + r"[/):]", tf_op):
            return scope
    return "no_scope" if tf_op else "no_tf_op"


def events(plane, line_name):
    """(start_ps, end_ps, event name, tf_op) of one line of a device plane."""
    stat_names = {i: m.name for i, m in plane.stat_metadata.items()}
    meta = {}
    for i, m in plane.event_metadata.items():
        tf_op = ""
        for s in m.stats:
            if stat_names.get(s.metadata_id) == "tf_op":
                tf_op = s.str_value or stat_names.get(s.ref_value, "")
        meta[i] = (m.name, tf_op)
    for line in plane.lines:
        if line.name != line_name:
            continue
        base = line.timestamp_ns * 1000
        for ev in line.events:
            a = base + ev.offset_ps
            yield (a, a + ev.duration_ps) + meta[ev.metadata_id]


def table(path: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        space = xplane_pb2.XSpace.FromString(f.read())
    plane = next(p for p in space.planes if p.name == "/device:TPU:0")
    runs = collections.defaultdict(list)
    for a, b, name, _ in events(plane, "XLA Modules"):
        runs[re.sub(r"\(.*\)$", "", name).strip()].append((a, b))
    ops = list(events(plane, "XLA Ops"))
    print(f"{len(ops)} operation events, {sum(1 for o in ops if o[3])} "
          f"with a tf_op stat")
    busiest = sorted(runs, key=lambda p: -sum(b - a for a, b in runs[p]))
    for program in busiest[:PROGRAMS_SHOWN]:
        mine = sorted(runs[program])
        by_scope = collections.defaultdict(list)
        kinds = collections.defaultdict(collections.Counter)
        spans = collections.Counter()
        for a, b, name, tf_op in ops:
            if any(ra <= a and b <= rb for ra, rb in mine):
                kind = (re.match(r"%?([A-Za-z_\-]*)", name).group(1)
                        or name[:20])
                if kind in CONTAINERS:
                    spans[kind] += b - a
                    continue
                scope = scope_of(tf_op)
                by_scope[scope].append((a, b))
                kinds[scope][kind] += b - a
        n, ms = len(mine), 1e9
        total = union_ps(iv for ivs in by_scope.values() for iv in ivs)
        median = sorted(b - a for a, b in mine)[n // 2]
        print(f"program {program}: {n} runs, median {median / ms:.3f} ms, "
              f"operations {total / ms / n:.3f} ms a run")
        for scope in sorted(by_scope, key=lambda s: -union_ps(by_scope[s])):
            busy = union_ps(by_scope[scope])
            top = ", ".join(f"{k} {v / ms / n:.2f}"
                            for k, v in kinds[scope].most_common(4))
            print(f"  {scope:14s} {busy / ms / n:8.3f} ms a run "
                  f"({100 * busy / total:5.1f}%)  [{top}]")
        for kind, ps in spans.most_common():
            print(f"  ({kind} events span {ps / ms / n:.3f} ms a run of the "
                  f"above: their bodies')")


if __name__ == "__main__":
    table(sys.argv[1])
