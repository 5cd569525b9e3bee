"""Device time by ``jax.named_scope``, read from the profile's op events.

On the TPU every operation of the "XLA Ops" lines has, among the stats of
its event metadata, the path of the JAX operation it was lowered from
(``jit(train_step)/.../moe.experts/ragged_dot:``), which holds the name
scopes the program opened around it, under transformations
(``transpose(jvp(moe.experts))``) too.  ``tracefile.load`` keeps only the
operations' HLO names, and ``jax.profiler.ProfileData`` shows only each
event's own stats, so ``load`` reads the metadata from the profile's
``XSpace`` protobuf (the few fields it needs, declared here) into the plain
trace's ``"scoped"`` key:

    {"paths": [path, ...], "ops": {"<chip>": [[start_ns, end_ns, i], ...]}}

with ``i`` an index into ``paths`` (the form a recorded trace under
``testdata/`` keeps them in).  A profile whose operations carry no such
paths gives no ops, and every reader here then returns None.
"""
from __future__ import annotations

import glob
import os
import re

import tracefile

# The metadata stat that holds an operation's JAX path.
PATH_STAT = "tf_op"
# Kernels that the TPU's lowering runs as custom calls whose path names the
# kernel alone (``ragged-dot-none:``, the grouped matmuls forward and back),
# each with the scope of the one program operation that lowers to it.
KERNEL_SCOPE = {"ragged-dot": "moe.experts"}

# Fields of tsl/profiler/protobuf/xplane.proto that ``load`` reads: (message,
# [(field, number, type, repeated, message type)]).  A map is read as its
# repeated entries.
_XPLANE = [
    ("XSpace", [("planes", 1, "message", True, "XPlane")]),
    ("XPlane", [("name", 2, "string", False, None),
                ("lines", 3, "message", True, "XLine"),
                ("event_metadata", 4, "message", True, "EventMetadataEntry"),
                ("stat_metadata", 5, "message", True, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, "int64", False, None),
                            ("value", 2, "message", False,
                             "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XStatMetadata")]),
    ("XLine", [("name", 2, "string", False, None),
               ("timestamp_ns", 3, "int64", False, None),
               ("events", 4, "message", True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, "int64", False, None),
                ("offset_ps", 2, "int64", False, None),
                ("duration_ps", 3, "int64", False, None)]),
    ("XEventMetadata", [("stats", 5, "message", True, "XStat")]),
    ("XStat", [("metadata_id", 1, "int64", False, None),
               ("str_value", 5, "string", False, None),
               ("ref_value", 7, "uint64", False, None)]),
    ("XStatMetadata", [("name", 2, "string", False, None)]),
]


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    types = {"string": F.TYPE_STRING, "int64": F.TYPE_INT64,
             "uint64": F.TYPE_UINT64, "message": F.TYPE_MESSAGE}
    fd = descriptor_pb2.FileDescriptorProto(name="bench_scopes_xplane.proto",
                                            package="bench_scopes_xplane",
                                            syntax="proto3")
    for name, fields in _XPLANE:
        m = fd.message_type.add(name=name)
        for fname, num, typ, rep, ref in fields:
            f = m.field.add(name=fname, number=num, type=types[typ],
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".bench_scopes_xplane." + ref
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_scopes_xplane.XSpace"))


def _paths(plane) -> dict[int, str]:
    """Event metadata id -> the JAX path among its stats."""
    stat_name = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for e in plane.event_metadata:
        for st in e.value.stats:
            if stat_name.get(st.metadata_id) == PATH_STAT:
                path = st.str_value or stat_name.get(st.ref_value, "")
                if path:
                    out[e.key] = path
    return out


def load(trace_dir: str) -> dict:
    out: dict = {"paths": [], "ops": {}}
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        return out
    space = _xspace_class()()
    with open(files[-1], "rb") as f:
        space.ParseFromString(f.read())
    index: dict[str, int] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = plane.name.rsplit(":", 1)[1]
        paths = _paths(plane)
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                p = paths.get(e.metadata_id)
                if not p:
                    continue
                if p not in index:
                    index[p] = len(out["paths"])
                    out["paths"].append(p)
                # Whole ns, as ``ProfileData`` gives the module spans.
                start = line.timestamp_ns + e.offset_ps // 1000
                out["ops"].setdefault(dev, []).append(
                    [start, start + e.duration_ps // 1000, index[p]])
    return out


def scoped_of(run) -> dict | None:
    """The scoped ops of a traced run, or None where it was not traced or
    its operations carry no paths.  Read once from the profile the harness
    wrote in its work directory, which lives until the run ends."""
    if run.trace is None:
        return None
    if "scoped" not in run.trace:
        import harness

        run.trace["scoped"] = load(os.path.join(harness.CHECKOUT,
                                                ".bench_work", "trace"))
    sc = run.trace["scoped"]
    return sc if any(sc["ops"].values()) else None


def has_scope(path: str, names) -> bool:
    """Whether one of ``names`` is a component of the path, or the scope
    of the kernel it names (``KERNEL_SCOPE``)."""
    parts = set(re.split(r"[/():]", path))
    parts |= {s for k, s in KERNEL_SCOPE.items() if path.startswith(k)}
    return bool(parts & set(names))


def per_step_ms(run, names, dev: str = "0") -> float | None:
    """Device ms a run of the train step spends in operations under any of
    the scopes ``names``, on one chip: the mean over the step's runs that
    lie wholly in the traced window."""
    sc = scoped_of(run)
    if sc is None:
        return None
    runs = tracefile.step_runs(run.trace, dev, *run.trace_window)
    if not runs:
        return None
    inside = [has_scope(p, names) for p in sc["paths"]]
    ops = sorted((s, e) for s, e, i in sc["ops"].get(dev, []) if inside[i])
    total = sum(min(e, hi) - max(s, lo) for lo, hi in runs for s, e in ops
                if s < hi and e > lo)
    return total / len(runs) / 1e6


def cell_config(metric: str):
    """(configuration, its module) of the cells that list ``metric`` in
    BENCHMARK.json, which must share one configuration."""
    import harness

    (m,) = [m for m in harness.benchmark()["per_layer"]
            if m["name"] == metric]
    (name,) = {harness.cell(c)["config"] for c in m["workloads"]}
    return harness.config(name)
