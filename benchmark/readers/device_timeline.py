"""The device's timeline as the engine knows it, inside the window: the
window's difference of ``metrics()["device_timeline_s"]`` (the always-on
counters ``serving.device.fed_s.<chunk|prefill>``,
``serving.device.starved_s.<admit|dispatch|harvest|outside>`` and
``serving.device.no_work_s``: fed from the return of an enqueue to the end
of the blocking read that waits it out, starved in between where the host
is, ``no_work`` with nothing unfinished). Args: ``parts`` (of ``fed.chunk``,
``fed.prefill``, ``starved.admit``, ``starved.dispatch``,
``starved.harvest``, ``starved.outside``, ``no_work``) and ``per``:
``"chunk"`` gives the parts' ms per chunk dispatch of the window (as
``phase_time_per_chunk``), ``"window"`` their percent of the window's wall
time, which is the difference of the sum of ALL parts: they tile wall time,
so the timeline is its own clock. An engine without the key reads None.

Once a run it also says two labelled lines: ``device_timeline`` (the
window's seconds by that clock, its chunk dispatches and every part in ms a
chunk, so that the tiling can be checked from the output) and
``timeline_stalls`` (the intervals of a second or more that the engine kept
since the window opened: ``metrics()["device_timeline_long"]`` entries whose
serial the window's first snapshot does not hold)."""

from benchmark.harness.common import say


def _say_once(ctx, m0, m1, t0, t1, chunks, wall):
    if ctx.get("device_timeline_said"):
        return
    ctx["device_timeline_said"] = True
    say("device_timeline", {
        "window_s": wall, "chunk_dispatches": chunks,
        "ms_per_chunk": {p: 1e3 * (t1[p] - t0[p]) / chunks for p in t1}
        if chunks > 0 else None})
    seen = {e["serial"] for e in m0.get("device_timeline_long", ())}
    say("timeline_stalls", [e for e in m1.get("device_timeline_long", ())
                            if e["serial"] not in seen])


def read(ctx, *, parts, per):
    eng = ctx.get("engine")
    if not eng:
        return None
    m0, m1 = eng["before"], eng["after"]
    t0, t1 = m0.get("device_timeline_s"), m1.get("device_timeline_s")
    if t0 is None or t1 is None:
        return None
    chunks = m1["chunk_dispatches"] - m0["chunk_dispatches"]
    wall = sum(t1.values()) - sum(t0.values())     # the parts tile it
    _say_once(ctx, m0, m1, t0, t1, chunks, wall)
    seconds = sum(t1[p] - t0[p] for p in parts)
    if per == "chunk":
        return 1e3 * seconds / chunks if chunks > 0 else None
    if per == "window":
        return 100.0 * seconds / wall if wall > 0 else None
    raise ValueError(f"per is 'chunk' or 'window', not {per!r}")
