"""The program's own counters (``repro.obs``), where it has them.

A program without ``repro.obs`` reads as None everywhere here, so the
readers built on this module report nothing for it.
"""


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def compile_seconds_before(t):
    """Seconds spent tracing, lowering and compiling (or loading from the
    cache) before the host-clock reading `t`, each second once (an inner
    trace runs inside its caller's), or None."""
    obs = _obs()
    if obs is None:
        return None
    kinds = set(obs.COMPILE_SECONDS.values())
    spans = sorted((end - s, end) for end, name, s in obs.events()
                   if name in kinds and end <= t)
    seconds, reach = 0.0, float("-inf")
    for lo, hi in spans:
        seconds += max(0.0, hi - max(lo, reach))
        reach = max(reach, hi)
    return seconds


def compiles_between(lo, hi):
    """Backend compiles (or cache loads) that ended inside [lo, hi], or
    None."""
    obs = _obs()
    if obs is None:
        return None
    return sum(1 for end, name, _ in obs.events()
               if name == "compile.backend_s" and lo <= end <= hi)
