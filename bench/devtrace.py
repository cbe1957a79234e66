"""Device trace of the measured window, and the arithmetic on it.

``record(dir)`` wraps the window in the JAX profiler; ``load`` reads the
``.xplane.pb`` it wrote into :class:`Events`: per device the operations
of its "XLA Ops" line, and the harness's own host spans (``bench.*``,
written by ``jax.profiler.TraceAnnotation``).  Everything is in seconds
on the profiler's clock.  ``Events`` round-trips through plain JSON, so
the tests check the arithmetic on a trace recorded on the chip.
"""
import contextlib
import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench."
COLLECTIVE_PERMUTE = "collective-permute"


@contextlib.contextmanager
def record(log_dir):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def op_name(event_name):
    """The HLO instruction's name: TPU traces name an op by its whole
    HLO line (``%copy.41 = f32[...] copy(...)``)."""
    m = re.match(r"\s*%?([^\s=]+)\s+=", event_name)
    return m.group(1) if m else event_name


def base_name(op):
    """``fusion.12`` -> ``fusion``: instances of one HLO op grouped."""
    return re.sub(r"(\.\d+)+$", "", op)


def leaves(ops):
    """The ops of a properly nested list (a ``while`` op encloses the ops
    of its body) that enclose no other."""
    out, stack = [], []
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] < e:  # ended, or only overlaps
            stack.pop()
        if stack:
            out[stack[-1]][3] = False
        out.append([n, s, e, True])
        stack.append(len(out) - 1)
    return [(n, s, e) for n, s, e, leaf in out if leaf]


class Events:
    def __init__(self, ops, host):
        #: {device id: [(name, start, end), ...]} sorted by start: the ops
        #: that enclose no other (a loop is not an op of its own; the ops
        #: of its body are)
        self.ops = {int(d): sorted(((op_name(n), s, e)
                                    for n, s, e in leaves(evs)),
                                   key=lambda o: o[1])
                    for d, evs in ops.items()}
        #: [(name, start, end), ...] of the harness's host spans
        self.host = sorted((tuple(e) for e in host), key=lambda o: o[1])

    @classmethod
    def load(cls, log_dir, device_ids):
        import jax

        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace under {log_dir}, found "
                               f"{paths}")
        data = jax.profiler.ProfileData.from_file(paths[0])
        ops, host = {}, []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m and int(m.group(1)) in device_ids:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops[int(m.group(1))] = [
                            (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in line.events
                             if e.name.startswith(HOST_PREFIX)]
        missing = set(device_ids) - set(ops)
        if missing:
            raise RuntimeError(f"no {OPS_LINE!r} line for devices "
                               f"{sorted(missing)} in the trace")
        return cls(ops, host)

    def to_json(self, path):
        with gzip.open(path, "wt") as f:
            json.dump({"ops": self.ops, "host": self.host}, f)

    @classmethod
    def from_json(cls, path):
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(d["ops"], d["host"])

    # -- the window and the device's busy time ---------------------------
    def window(self):
        """(start, end) of the harness's host spans: the measured window."""
        if not self.host:
            raise RuntimeError("no bench.* host spans in the trace")
        return (min(s for _, s, _ in self.host),
                max(e for _, _, e in self.host))

    def ops_in(self, device, lo, hi, pick=None):
        """Operations of `device` that overlap [lo, hi], clipped to it."""
        return [(n, max(s, lo), min(e, hi)) for n, s, e in self.ops[device]
                if e > lo and s < hi and (pick is None or pick(n))]

    def busy(self, device, lo=None, hi=None, pick=None):
        if lo is None:
            lo, hi = self.window()
        return total(union(self.ops_in(device, lo, hi, pick)))

    def busy_s(self):
        """Busy seconds in the window, averaged over the devices."""
        return sum(self.busy(d) for d in self.ops) / len(self.ops)

    def window_s(self):
        lo, hi = self.window()
        return hi - lo

    def idle_share(self):
        return 1.0 - self.busy_s() / self.window_s()

    def op_count(self):
        """Operations that start in the window, averaged over devices."""
        lo, hi = self.window()
        return sum(sum(1 for _, s, _ in evs if lo <= s < hi)
                   for evs in self.ops.values()) / len(self.ops)

    def exposed(self, device, pick):
        """Seconds in which an op `pick` selects runs on `device` and no
        other op does."""
        lo, hi = self.window()
        mine = union(self.ops_in(device, lo, hi, pick))
        rest = union(self.ops_in(device, lo, hi, lambda n: not pick(n)))
        return total(mine) - total(intersect(mine, rest))

    # -- what the next issue reads -----------------------------------------
    def breakdown(self, top=10):
        """The device ops that took most time (seconds per device, by
        base name), and the longest idle gaps of device 0's busy time by
        the host span that overlaps each most."""
        lo, hi = self.window()
        per_op = {}
        for d in self.ops:
            for n, s, e in self.ops_in(d, lo, hi):
                per_op[base_name(n)] = per_op.get(base_name(n), 0.0) + e - s
        ops = sorted(((n, t / len(self.ops)) for n, t in per_op.items()),
                     key=lambda x: -x[1])[:top]
        dev = min(self.ops)
        gaps = complement(union(self.ops_in(dev, lo, hi)), lo, hi)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[self.host_at(s, e), e - s] for s, e in gaps]}

    def host_at(self, lo, hi):
        best, name = 0.0, "none"
        for n, s, e in self.host:
            ov = min(e, hi) - max(s, lo)
            if ov > best:
                best, name = ov, n
        return name


def union(intervals):
    """Merged [(start, end)] of (name, start, end) or (start, end)."""
    spans = sorted((iv[-2], iv[-1]) for iv in intervals if iv[-1] > iv[-2])
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(spans):
    return sum(e - s for s, e in spans)


def intersect(a, b):
    """Intersection of two merged span lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(spans, lo, hi):
    """The gaps of a merged span list inside [lo, hi]."""
    out, t = [], lo
    for s, e in spans:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def is_collective_permute(name):
    return name.startswith(COLLECTIVE_PERMUTE)
