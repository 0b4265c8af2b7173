"""Profiler capture of the measured window and its reduction to device
busy time, kernel time, the top device operations and the idle gaps,
each gap labelled by what the host was doing.

The benchmark marks the window and its calls into each layer with
``jax.profiler.TraceAnnotation`` spans named ``<layer>.<what>``
(``LABEL_PREFIXES``); they land on the host plane of the same trace.
A kernel is found by its device operation: a Mosaic custom call
(``tpu_custom_call``) with a given number of outputs.  The program gives
its kernels no name that reaches the trace, so the output count is the
most stable mark the trace carries; the harness stops a traced run whose
kernel count departs from the calls the window made
(``harness.check_kernel_calls``), so a kernel that drops out of this
mark's sight cannot vanish from the metrics unnoticed.
"""

from __future__ import annotations

import contextlib
import pathlib
import re
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
#: host annotations that label idle gaps (the benchmark's own spans)
LABEL_PREFIXES = ("bench.", "broker.", "sweep.")
TOP = 10


class Capture:
    """One profiler session over the measured window."""

    def __init__(self) -> None:
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._window = None

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        # Python function tracing would multiply the host's time
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = self.annotate(WINDOW)
        self._window.__enter__()

    def close_window(self) -> None:
        """End the measured window; the trace goes on until ``stop``."""
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    def stop(self) -> None:
        import jax
        self.close_window()
        jax.profiler.stop_trace()

    @staticmethod
    def annotate(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def reduce(self, kernels: dict, device_ids) -> dict:
        from jax.profiler import ProfileData
        try:
            files = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError(f"the profiler wrote no trace under "
                                   f"{self.dir}")
            return reduce_space(ProfileData.from_file(str(files[-1])),
                                kernels, device_ids)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def maybe(capture, name: str):
    """An annotation when tracing, nothing otherwise."""
    return capture.annotate(name) if capture is not None \
        else contextlib.nullcontext()


def _union(intervals: list) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def custom_call_outputs(op: str):
    """Output count of a Mosaic kernel's device operation, from its HLO
    text ``%name = (out, out, ...) custom-call(...)``; None for any
    other operation."""
    if 'custom_call_target="tpu_custom_call"' not in op:
        return None
    return op.split(" custom-call(")[0].count("]{")


_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def short_name(op: str, kernels: dict) -> str:
    """``%name opcode`` of an HLO operation's text, with the kernel it
    is when it is one (``%tpu_custom_call.1 custom-call mesi_tick``)."""
    head, _, rest = op.partition(" = ")
    found = _OPCODE.search(rest)
    if not rest or found is None:
        return op[:120]
    outputs = custom_call_outputs(op)
    kernel = [k for k, n in kernels.items() if outputs == n]
    return " ".join([head, found.group(1)] + kernel)


def reduce_space(space, kernels: dict, device_ids) -> dict:
    """Reduce a ``ProfileData`` to the window's device metrics.

    ``kernels`` maps a kernel name to the output count of its device
    operation (``custom_call_outputs``); ``device_ids`` are the chips
    the cell uses.  Times are seconds: ``busy_s`` is averaged over those
    chips, each kernel's time is summed over them (device-seconds).
    """
    wanted = {f"{DEVICE_PREFIX}{i}" for i in device_ids}
    labels, window = [], None
    device_planes = []
    for plane in space.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
                    if ev.name.startswith(LABEL_PREFIXES):
                        labels.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name in wanted:
            device_planes.append(plane)
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} annotation in the trace")
    t0, t1 = window

    busy_total = 0.0
    kernel_ns = {k: 0.0 for k in kernels}
    kernel_events = {k: 0 for k in kernels}
    ops: dict = {}
    gaps: list = []
    for plane in device_planes:
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, t0), min(ev.end_ns, t1)
                if e <= s:
                    continue
                intervals.append((s, e))
                ops[ev.name] = ops.get(ev.name, 0.0) + (e - s)
                outputs = custom_call_outputs(ev.name)
                for k, n_out in kernels.items():
                    if outputs == n_out:
                        kernel_ns[k] += e - s
                        kernel_events[k] += 1
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(ge - gs, (gs + ge) / 2)
                 for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
    if busy_total == 0:
        raise RuntimeError("no device operation ran in the window")
    n_devices = len(device_planes)
    ns = 1e-9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (t1 - t0) * ns,
        "busy_s": busy_total * ns / n_devices,
        "devices": n_devices,
        "kernel_s": {k: v * ns for k, v in kernel_ns.items()},
        "kernel_events": kernel_events,
        "device_ops": [[short_name(name, kernels), v * ns / n_devices]
                       for name, v in top_ops],
        "idle_gaps": [[_label(mid, labels), g * ns] for g, mid in
                      sorted(gaps, key=lambda gm: -gm[0])[:TOP]],
    }


def _label(t: float, labels: list) -> str:
    """The innermost benchmark annotation open at time ``t``."""
    best = None
    for s, e, name in labels:
        if s <= t <= e and (best is None or s > best[0]):
            best = (s, name)
    return best[1] if best is not None else "host: outside any span"
