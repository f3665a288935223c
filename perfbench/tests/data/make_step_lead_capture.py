"""Writes ``step_lead_capture.xplane.pb``: the small synthetic capture that
``perfbench/tests/test_step_lead.py`` reads ``trace_step_lead`` from. Run
once, by hand (it needs tensorflow's copy of the xplane proto; the test
does not):  python3 perfbench/tests/data/make_step_lead_capture.py

Times in microseconds. One device, five step modules back to back but for
one hole, and a small module that is no step:

    decode_window   0-1000     touches the capture's start
    mixed_step   1000-1500
    decode_window 1500-2500
    convert      2500-2510     (not a step module)
    decode_window 2600-3600    the chip stood 90 us: the host came late
    prefill_step 3600-4000     touches the capture's end

and on the step loop's thread six dispatch spans:

    50-90     decode   the next step module to start is the MIXED one: left out
    200-260   mixed    -> 1000, lead 740
    1100-1150 decode   -> 1500, lead 350
    1300-1320 (no kind: a program older than the span's arguments) ignored
    2560-2590 decode   -> 2600, lead 10
    2700-2750 prefill  -> 3600, its module touches the end: dropped

The median of 740, 350, 10 is 350 us.
"""

from pathlib import Path

from tensorflow.tsl.profiler.protobuf import xplane_pb2

US = 1000       # ns


def _plane(space, name):
    plane = space.planes.add()
    plane.name = name
    return plane, {}, {}


def _event(plane, names, stat_names, line, name, start_us, dur_us, **stats):
    if name not in names:
        names[name] = len(names) + 1
        plane.event_metadata[names[name]].id = names[name]
        plane.event_metadata[names[name]].name = name
    ev = line.events.add()
    ev.metadata_id = names[name]
    ev.offset_ps = start_us * US * 1000
    ev.duration_ps = dur_us * US * 1000
    for key, value in stats.items():
        if key not in stat_names:
            stat_names[key] = len(stat_names) + 1
            plane.stat_metadata[stat_names[key]].id = stat_names[key]
            plane.stat_metadata[stat_names[key]].name = key
        st = ev.stats.add()
        st.metadata_id = stat_names[key]
        if isinstance(value, str):
            st.str_value = value
        else:
            st.int64_value = value


def main():
    space = xplane_pb2.XSpace()
    dev, names, stat_names = _plane(space, "/device:TPU:0")
    modules = [("jit_decode_window_greedy(11)", 0, 1000),
               ("jit_mixed_step(12)", 1000, 500),
               ("jit_decode_window_greedy(11)", 1500, 1000),
               ("jit_convert_element_type(13)", 2500, 10),
               ("jit_decode_window_greedy(11)", 2600, 1000),
               ("jit_prefill_step(14)", 3600, 400)]
    for i, line_name in enumerate(("XLA Modules", "XLA Ops")):
        line = dev.lines.add()
        line.id, line.name = i + 1, line_name
        for name, start, dur in modules:
            _event(dev, names, stat_names, line,
                   name if i == 0 else "%fusion.1 = f32[] fusion()",
                   start, dur)
    host, names, stat_names = _plane(space, "/host:CPU")
    line = host.lines.add()
    line.id, line.name = 1, "python3"
    _event(host, names, stat_names, line, "kgct.step", 0, 4000,
           launched=8, retired=7)
    for start, dur, kind, step in ((50, 40, "decode", 7),
                                   (200, 60, "mixed", 8),
                                   (1100, 50, "decode", 9),
                                   (1300, 20, None, 0),
                                   (2560, 30, "decode", 10),
                                   (2700, 50, "prefill", 11)):
        stats = {} if kind is None else {"step": step, "kind": kind,
                                         "rows": 64}
        _event(host, names, stat_names, line, "kgct.device_dispatch",
               start, dur, **stats)
    out = Path(__file__).with_name("step_lead_capture.xplane.pb")
    out.write_bytes(space.SerializeToString())
    print(out, out.stat().st_size, "bytes")


if __name__ == "__main__":
    main()
