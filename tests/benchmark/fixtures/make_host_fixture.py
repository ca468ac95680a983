"""Cut a recorded ``*.xplane.pb`` down to a fixture that keeps the
program's host spans.

    python tests/benchmark/fixtures/make_host_fixture.py <in.xplane.pb> <out.xplane.pb.gz> <op_names.json> [max_executions] [name_chars]

Like ``make_fixture.py`` (which keeps of ``/host:CPU`` the ``bench.*``
annotations only, and is not edited): of every ``/device:TPU:<n>`` plane
the lines ``XLA Ops`` and ``XLA Modules``, optionally only up to the
start of execution number ``max_executions`` + 1 of the round program;
of ``/host:CPU`` the ``bench.*`` AND ``round.*`` events, each line kept
as a line of its own (``harness/host_spans.py`` tells the dispatching
thread from the prefetch worker by the line) with the events' stats (the
spans' ``round``, ``what`` and ``fuse`` arguments) and the stat metadata
they refer to. No device op is thinned out: the tests hold the readers'
sums against ``scope_ms_round`` and ``device_idle_pct`` on the same
events. Times are left as recorded; an op event's name (its
instruction's whole HLO text) is cut to ``name_chars``. The traced run's
``<kept>.op_names.json`` (``run.py --keep-trace``) is cut to the
instructions the fixture has and written beside it as
``<out minus .xplane.pb.gz>.op_names.json.gz``. Needs the
``xplane_pb2`` module that ships with tensorflow; the tests read the
result with ``jax.profiler.ProfileData`` alone.
"""

import gzip
import json
import re
import sys

HOST_PREFIXES = ("bench.", "round.")


def main(src: str, dst: str, op_names: str, max_executions: int = 0,
         name_chars: int = 0) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    instructions = set()
    for plane in space.planes:
        device = re.match(r"^/device:TPU:\d+$", plane.name)
        if not device and plane.name != "/host:CPU":
            continue
        kept = out.planes.add()
        kept.id, kept.name = plane.id, plane.name
        for key, meta in plane.stat_metadata.items():
            kept.stat_metadata[key].CopyFrom(meta)
        cut_ps = None
        if device and max_executions:
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                starts = sorted(
                    line.timestamp_ns * 1000 + e.offset_ps for e in line.events
                    if plane.event_metadata[e.metadata_id].name.startswith("jit_round_fn")
                )
                if len(starts) > max_executions:
                    cut_ps = starts[max_executions] + 1
        used = set()
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            new = xplane_pb2.XLine()
            new.id, new.name = line.id, line.name
            new.display_name = line.display_name
            new.timestamp_ns = line.timestamp_ns
            for event in line.events:
                name = plane.event_metadata[event.metadata_id].name
                if not device and not name.startswith(HOST_PREFIXES):
                    continue
                start_ps = line.timestamp_ns * 1000 + event.offset_ps
                if cut_ps is not None and start_ps >= cut_ps:
                    continue
                new.events.add().CopyFrom(event)
                used.add(event.metadata_id)
                if device and line.name == "XLA Ops":
                    instructions.add(name.lstrip("%").split(" ")[0])
            if new.events:
                kept.lines.add().CopyFrom(new)
        for key in used:
            kept.event_metadata[key].CopyFrom(plane.event_metadata[key])
            if device and name_chars:
                meta = kept.event_metadata[key]
                meta.name = meta.name[:name_chars]
                meta.display_name = meta.display_name[:name_chars]
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(out.SerializeToString())
    with open(op_names) as f:
        names = {k: v for k, v in json.load(f).items() if k in instructions}
    with gzip.open(dst[:-len(".xplane.pb.gz")] + ".op_names.json.gz", "wt",
                   compresslevel=9) as f:
        json.dump(names, f)


if __name__ == "__main__":
    main(*sys.argv[1:4], *(int(a) for a in sys.argv[4:6]))
