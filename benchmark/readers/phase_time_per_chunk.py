"""Host time of named phases of ``ServingEngine.step`` per chunk dispatch,
inside the window, in ms: the window's difference of the phases' summed
seconds (``metrics()["step_phase_s"][phase]["sum"]``, the always-on
histograms ``serving.step.phase_s.<phase>``) over the window's difference
of ``chunk_dispatches``. Args: ``phases`` (of ``admit``, ``dispatch``,
``wait``, ``harvest``, which tile the step) and ``minus`` (of
``admit_wait``, the part of ``admit`` the host spends blocked on the
row-key readback behind the prefill). ``wait`` and ``admit_wait`` are the
host blocked on a busy device, so a host metric leaves the first out and
takes the second off. A step that dispatched no chunk still adds its
``admit`` time; the benchmark's loop steps only while a request is in
flight, so there are few. An engine without the counters reads None."""


def read(ctx, *, phases, minus=()):
    eng = ctx.get("engine")
    if not eng:
        return None
    m0, m1 = eng["before"], eng["after"]
    p0, p1 = m0.get("step_phase_s"), m1.get("step_phase_s")
    if p0 is None or p1 is None:
        return None
    chunks = m1["chunk_dispatches"] - m0["chunk_dispatches"]
    if chunks <= 0:
        return None

    def seconds(names):
        return sum(p1[p]["sum"] - p0[p]["sum"] for p in names)
    return 1e3 * (seconds(phases) - seconds(minus)) / chunks
