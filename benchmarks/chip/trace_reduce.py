"""From a traced run to the numbers the per-layer readers report.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Its device
planes (``/device:TPU:<n>``) carry two lines this file reads:

* ``XLA Modules``: one event per jitted program run -- the table steps show
  as ``jit__table_step(...)`` and ``jit__table_step_pieces(...)``;
* ``XLA Ops``: one event per HLO op run, nested (a ``while`` op spans the
  ops of its body).  The Lloyd kernel's ``pallas_call`` shows as
  ``%kmeans_assign_pallas[.n] = ... custom-call(...)``: it carries no
  ``name=`` of its own, so the jitted wrapper's name is what marks it.

Host spans come from the program's own flight recorder (``repro.obs``,
``perf_counter_ns``, the same monotonic clock as the window).  The
profiler's host annotations of the table steps (``symed.table_step*``,
from ``Observability(jax_annotate=True)``) sit beside the program's
``stream.dispatch*`` spans, which gives the offset between the two clocks.
"""
from __future__ import annotations

import glob
import json
import pathlib
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
STEP_MODULES = ("jit__table_step(", "jit__table_step_pieces(")
KERNEL_PREFIX = "%kmeans_assign_pallas"
HOST_LAYERS = ("transport.decode", "transport.route", "stream.pack",
               "stream.pack_pieces", "stream.dispatch",
               "stream.dispatch_pieces", "stream.harvest",
               "stream.harvest_pieces", "stream.dtw_monitor")


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def lloyd_kernel_cost(slots: int, n_max: int, k_max: int,
                      d: int = 2, block_n: int = 512) -> Tuple[float, float]:
    """(operations, bytes) of one Lloyd ``pallas_call`` over the table.

    Operations are what the algorithm needs: the distance cross term
    (``2 N K D`` multiply-adds as flops) and the per-cluster statistics
    (``2 N K (D + 1)``), per slot, at the unpadded sizes.  Bytes are the
    operands and results as the call moves them through HBM: the
    feature-major points with their mask row (``Dp x Np``) and centers
    (``Dp x Kp``) in, labels (``Np``) and statistics (``Dp x Kp``) out, f32
    and i32, ``Dp = round_up(D + 1, 8)``, ``Kp = round_up(K, 128)``.
    """
    up = lambda v, m: (v + m - 1) // m * m  # noqa: E731
    dp, kp = up(d + 1, 8), up(k_max, 128)
    bn = min(up(block_n, 128), up(n_max, 128))
    np_ = up(n_max, bn)
    ops = slots * (2 * n_max * k_max * d + 2 * n_max * k_max * (d + 1))
    nbytes = 4 * slots * (dp * np_ + dp * kp + np_ + dp * kp)
    return float(ops), float(nbytes)


class Context:
    """Everything a per-layer reader may read, for one traced window."""

    def __init__(self, trace_dir, window, spans, cfg, loadgen, device_kind):
        self.window = window                    # monotonic seconds
        self.window_s = window[1] - window[0]
        self.cfg, self.loadgen = cfg, loadgen
        self.device_kind = device_kind
        self.spans = [(n, t0 / 1e9, (t0 + d) / 1e9) for n, ph, t0, d, _
                      in spans if ph == "X"
                      and window[0] <= t0 / 1e9 <= window[1]]
        files = sorted(glob.glob(str(pathlib.Path(trace_dir) / "**"
                                     / "*.xplane.pb"), recursive=True))
        self.devices: List[dict] = []
        self.annotations: List[Tuple[str, float, float]] = []
        if files:
            self._read(files[-1])

    def _read(self, path: str) -> None:
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                self.devices.append(_device_plane(plane))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("symed.table_step"):
                            self.annotations.append(
                                (e.name, e.start_ns / 1e9,
                                 (e.start_ns + e.duration_ns) / 1e9))

    # -- helpers the readers share ---------------------------------------

    def step_ms(self) -> Optional[float]:
        durs = [d for dev in self.devices for d in dev["steps"]]
        return 1e3 * float(np.mean(durs)) if durs else None

    def kernel_calls(self) -> List[float]:
        return [d for dev in self.devices for d in dev["kernel"]]

    def host_spans(self, *names: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.spans if n in names]

    def steps_dispatched(self) -> int:
        return len(self.host_spans("stream.dispatch", "stream.dispatch_pieces"))

    def device_summary(self):
        """(busy_s, window_s, breakdown) averaged over the traced chips."""
        if not self.devices:
            raise RuntimeError("the trace holds no TPU device plane")
        busy = float(np.mean([dev["busy_s"] for dev in self.devices]))
        ops: Dict[str, float] = {}
        for dev in self.devices:
            for name, s in dev["self_s"].items():
                ops[name] = ops.get(name, 0.0) + s / len(self.devices)
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = self._labelled_gaps()[:10]
        return busy, self.window_s, {"device_ops": [list(t) for t in top],
                                     "idle_gaps": gaps}

    def _clock_offset(self) -> Optional[float]:
        """Trace clock minus monotonic clock, from the step annotations."""
        disp = sorted(t0 for n, t0, _ in self.spans
                      if n.startswith("stream.dispatch"))
        ann = sorted(t0 for _, t0, _ in self.annotations)
        if not disp or not ann:
            return None
        guess = ann[0] - disp[0]
        diffs = [a - min(disp, key=lambda d: abs(a - guess - d)) for a in ann]
        return float(np.median(diffs))

    def _labelled_gaps(self) -> List[list]:
        off = self._clock_offset()
        out = []
        for dev in self.devices:
            for g0, g1 in dev["gaps"]:
                label = "host: no span open"
                if off is not None:
                    mid = (g0 + g1) / 2 - off
                    hits = [(t1 - t0, n) for n, t0, t1 in self.spans
                            if t0 <= mid <= t1 and n in HOST_LAYERS]
                    if hits:
                        label = "host: " + min(hits)[1]
                out.append([label, g1 - g0])
        return sorted(out, key=lambda g: -g[1])


def _op_name(full: str) -> str:
    head = full.split(" = ", 1)[0].lstrip("%")
    return head


def _device_plane(plane) -> dict:
    """Steps, kernel calls, busy time, op self times and idle gaps."""
    lines = {line.name: line for line in plane.lines}
    steps = []
    if "XLA Modules" in lines:
        for e in lines["XLA Modules"].events:
            if e.name.startswith(STEP_MODULES):
                steps.append(e.duration_ns / 1e9)
    kernel, self_s, gaps = [], {}, []
    busy = 0.0
    if "XLA Ops" in lines:
        stack: List[list] = []      # [end_ns, name, child_ns]
        cur_end = None
        start0 = None
        for e in lines["XLA Ops"].events:
            s, d = e.start_ns, e.duration_ns
            end = s + d
            name = e.name
            if name.startswith(KERNEL_PREFIX):
                kernel.append(d / 1e9)
            while stack and stack[-1][0] <= s:
                top = stack.pop()
                self_s[top[1]] = self_s.get(top[1], 0.0) + top[2]
            if stack:
                stack[-1][2] -= d / 1e9
            stack.append([end, _op_name(name), d / 1e9])
            if cur_end is None:
                start0, cur_end = s, end
            elif s > cur_end:
                busy += (cur_end - start0) / 1e9
                gaps.append((cur_end / 1e9, s / 1e9))
                start0, cur_end = s, end
            else:
                cur_end = max(cur_end, end)
        while stack:
            top = stack.pop()
            self_s[top[1]] = self_s.get(top[1], 0.0) + top[2]
        if cur_end is not None:
            busy += (cur_end - start0) / 1e9
    return {"steps": steps, "kernel": kernel, "busy_s": busy,
            "self_s": self_s, "gaps": gaps}
