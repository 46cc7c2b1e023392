"""Build small ``.xplane.pb`` files for the trace tests.

Device planes are named ``/device:TPU:<n>`` with an ``XLA Ops`` line whose
event names are HLO text, as the TPU profiler writes them; host spans go on
a ``/host:CPU`` thread line.  Times are in nanoseconds.
"""

import json

from jax.profiler import ProfileData


def hlo(name, opcode, extra=""):
    """HLO text of an op, the way an ``XLA Ops`` event is named."""
    return f"%{name} = f32[1024,1024]{{1,0:T(8,128)}} {opcode}(f32[] %p){extra}"


KERNEL = ', custom_call_target="tpu_custom_call"'


def xspace(devices, spans):
    """``devices``: {id: [(hlo text, start, duration)]};
    ``spans``: [(name, start, duration)].  Returns serialized XSpace."""
    out, names = [], {}

    def meta(name):
        return names.setdefault(name, len(names) + 1)

    def plane(pid, pname, lname, events):
        evs = "".join(
            f"events {{ metadata_id: {meta(n)} offset_ps: {int(s * 1000)} "
            f"duration_ps: {int(d * 1000)} }}\n" for n, s, d in events)
        md = "".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{json.dumps(n)} }} }}\n" for n, i in names.items())
        return (f"planes {{ id: {pid} name: {json.dumps(pname)}\n"
                f"lines {{ id: 1 name: {json.dumps(lname)} timestamp_ns: 0\n"
                f"{evs}}}\n{md}}}\n")

    for dev, events in sorted(devices.items()):
        names.clear()
        out.append(plane(dev + 1, f"/device:TPU:{dev}", "XLA Ops", events))
    names.clear()
    out.append(plane(100, "/host:CPU", "main/1",
                     [(f"bench.{n}", s, d) for n, s, d in spans]))
    return ProfileData.text_proto_to_serialized_xspace("".join(out))


def write(path, devices, spans):
    with open(path, "wb") as f:
        f.write(xspace(devices, spans))
    return path
