"""Share of slots holding a live row per chunk dispatch, inside the
window: the engine observes ``occupied / num_slots`` once per chunk into a
histogram; ``metrics()`` gives its mean and count, so the window's own mean
is the difference of the sums over the difference of the counts (not the
engine's lifetime mean, which holds the warm-up). In percent."""


def read(ctx):
    eng = ctx.get("engine")
    if not eng:
        return None
    m0, m1 = eng["before"], eng["after"]
    n = m1["occupancy_samples"] - m0["occupancy_samples"]
    if n <= 0:
        return None
    s0 = (m0["occupancy_mean"] * m0["occupancy_samples"]
          if m0["occupancy_samples"] else 0.0)
    s1 = m1["occupancy_mean"] * m1["occupancy_samples"]
    return 100.0 * (s1 - s0) / n
