"""Cut a recorded ``*.xplane.pb`` down to a test fixture.

    python tests/benchmark/fixtures/make_fixture.py <in.xplane.pb> <out.xplane.pb.gz> [max_executions] [name_chars] [min_op_ns]

Keeps what ``benchmark/harness/trace_reduce.py`` reads and nothing else:
of every ``/device:TPU:<n>`` plane the lines ``XLA Ops`` and ``XLA
Modules`` (optionally only up to the start of execution number
``max_executions`` + 1 of the round program, so that a whole number of
periods stays), of ``/host:CPU`` the ``bench.*`` annotations; event
metadata nothing refers to is dropped. Times and stats are left as
recorded. An op event's name is its instruction's whole HLO text, some
hundreds of characters of operand shapes; with ``name_chars`` it is cut to
that many (the reduction reads the instruction name at its head only),
which is what makes a fixture small enough to commit. With ``min_op_ns``
the device ops shorter than that are thinned out, except collectives and
loops (a four-chip trace of a real cell has 25,000 ops per execution and
chip, most of them under a microsecond): what is left is still recorded
events at their recorded times, and ``expected`` below is worked out from
the same events. Needs the ``xplane_pb2`` module that ships with
tensorflow; only this tool does, the tests read the result with
``jax.profiler.ProfileData`` alone.
"""

import gzip
import re
import sys


def main(src: str, dst: str, max_executions: int = 0,
         name_chars: int = 0, min_op_ns: int = 0) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
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
                    # one past the last execution's start, so that it stays
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
                if not device and not name.startswith("bench."):
                    continue
                start_ps = line.timestamp_ns * 1000 + event.offset_ps
                if cut_ps is not None and start_ps >= cut_ps:
                    continue
                if (device and line.name == "XLA Ops"
                        and event.duration_ps < min_op_ns * 1000
                        and not re.match(r"^%?(all-|reduce-scatter|collective|"
                                         r"while)", name)):
                    continue
                new.events.add().CopyFrom(event)
                used.add(event.metadata_id)
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


def expected(fixture_gz: str, op_names_json: str, scopes) -> dict:
    """The numbers ``expected.json`` holds for a fixture, worked out here
    the long way round, independently of ``trace_reduce``: time is cut at
    every event boundary and each elementary slice is looked at on its
    own (is any op running? which is the innermost one that has a scope?
    is a collective in flight? does a compute op run?). Quadratic, fine for a fixture.
    The window and the periods are the round program's execution starts,
    read off the ``XLA Modules`` line, the first left out as the
    reduction's definition of the steady window has it."""
    import json
    import tempfile

    from jax.profiler import ProfileData

    with gzip.open(fixture_gz) as f, tempfile.NamedTemporaryFile(
            suffix=".xplane.pb") as tmp:
        tmp.write(f.read())
        tmp.flush()
        data = ProfileData.from_file(tmp.name)
    with open(op_names_json) as f:
        op_names = json.load(f)
    coll = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                      r"collective-permute|all-to-all)")
    out = {"devices": 0, "scopes": list(scopes), "values": {
        "periods": [], "window_ns": [], "busy_ns": [],
        "local_train_self_ns": [], "unscoped_self_ns": [],
        "collective_ns": [], "collective_exposed_ns": [],
        "host_annotations": []}}
    v = out["values"]
    for plane in data.planes:
        if plane.name == "/host:CPU":
            v["host_annotations"] = sorted({
                e.name for line in plane.lines for e in line.events})
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        out["devices"] += 1
        lines = {line.name: list(line.events) for line in plane.lines}
        starts = sorted(e.start_ns for e in lines["XLA Modules"]
                        if e.name.startswith("jit_round_fn"))
        starts = starts[1:]  # the first execution began before the trace
        lo, hi = starts[0], starts[-1]
        ops = []
        for e in lines["XLA Ops"]:
            name = e.name.lstrip("%").split(" ")[0]
            path = op_names.get(name, "").split("/")
            scope = next((p for p in reversed(path) if p in scopes), "")
            ops.append((e.start_ns, e.start_ns + e.duration_ns, name, scope))
        flights = [(e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines.get("Async XLA Ops", [])
                   if coll.match(e.name.lstrip("%"))]
        flights += [(s, e) for s, e, n, _ in ops
                    if coll.match(n) and "-start" not in n and "-done" not in n]
        cuts = sorted({lo, hi} | {t for s, e, *_ in ops for t in (s, e)
                                  if lo < t < hi}
                      | {t for s, e in flights for t in (s, e) if lo < t < hi})
        busy = local = unscoped = in_flight = exposed = 0.0
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            running = [o for o in ops if o[0] <= mid < o[1]]
            flying = any(s <= mid < e for s, e in flights)
            in_flight += (b - a) if flying else 0
            computing = any(not coll.match(o[2]) and not re.match(
                r"^(while|conditional|call)(\.|$)", o[2]) for o in running)
            exposed += (b - a) if flying and not computing else 0
            if running:
                busy += b - a
                # innermost first; the first that has a scope names the slice
                nested = sorted(running, key=lambda o: (-o[0], o[1]))
                scope = next((o[3] for o in nested if o[3]), "")
                local += (b - a) if scope == "round_local_train" else 0
                unscoped += (b - a) if scope == "" else 0
        v["periods"].append(len(starts) - 1)
        v["window_ns"].append(hi - lo)
        v["busy_ns"].append(busy)
        v["local_train_self_ns"].append(local)
        v["unscoped_self_ns"].append(unscoped)
        v["collective_ns"].append(in_flight)
        v["collective_exposed_ns"].append(exposed)
    return out


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:6]))
