"""What a per-layer metric's reader is given: the configuration and
traffic files, the window, the reduced trace of its traced slice, and
the executor calls that started and ended inside that slice."""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Any, Dict, Optional

from chipbench import costs
from chipbench import trace as trace_mod

KERNELS = Path(__file__).resolve().parent / "kernels"


def kernel_cost(kernel: str):
    """``cost(shapes) -> (flops, bytes)`` from ``kernels/<kernel>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_kernel_{kernel}", KERNELS / f"{kernel}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.cost


class MetricContext:
    def __init__(self, cfg, traffic, wr, trace, calls, subnets,
                 peaks: Dict[str, Any]):
        self.cfg, self.traffic, self.window = cfg, traffic, wr
        self.trace, self.subnets, self.peaks = trace, subnets, peaks
        self.seq = int(traffic["prompt_len"])
        lo, hi = wr.trace_slice
        self.calls = [c for c in calls if lo <= c.t0 and c.t1 <= hi]
        self.modules = trace_mod.modules_in(trace)

    def call_flops(self, c) -> float:
        return costs.prefill_flops(self.cfg, self.subnets[c.subnet], c.rows,
                                   self.seq)

    def device_s_per_call(self) -> Optional[float]:
        """Mean device time of one program execution in the slice."""
        if not self.modules:
            return None
        return sum((m.end - m.start) for m in self.modules) * 1e-9 \
            / len(self.modules)

    def kernel_share(self, kernel: str) -> Optional[float]:
        """Roofline share (%) of a kernel over the slice: the least time
        the chip could take for its calls (the larger of its FLOPs at peak
        and its bytes at peak bandwidth) over the time they took."""
        cost = kernel_cost(kernel)
        need = took = 0.0
        for ev in trace_mod.kernel_events(self.trace, kernel):
            flops, nbytes = cost(trace_mod.parse_op(ev.name)[2])
            need += max(flops / self.peaks["bf16_flops_per_s"],
                        nbytes / self.peaks["hbm_bytes_per_s"])
            took += (ev.end - ev.start) * 1e-9
        return 100.0 * need / took if took > 0 else None
