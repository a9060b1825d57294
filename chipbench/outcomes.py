"""What became of each query of the window, and the rows kept for the
correctness check.

Each attempted query ends in exactly one outcome:

- ``ANSWERED``: a logits row came back (a miss if after the SLO);
- ``DROPPED``: the engine refused it as infeasible and resolved it to
  ``(None, 0.0)``; a miss, and no failure;
- ``FAILED``: the future raised, the row was malformed or not finite, or
  the query was still unresolved when the window's wait ran out.

Only answered rows can be kept, and only those a seeded reservoir picks:
at most ``cap`` per served subnet, each copied out of its batch array so
that the batch can be freed as soon as the router lets go of it.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

PENDING, ANSWERED, DROPPED, FAILED = 0, 1, 2, 3


class Reservoir:
    """Per-subnet reservoir sample (Algorithm R) of answered rows."""

    def __init__(self, cap: int, seed: int):
        self.cap = int(cap)
        self.rng = np.random.default_rng([seed, 3])
        self.seen: Dict[int, int] = {}
        self.rows: Dict[int, List[Tuple[int, np.ndarray]]] = {}

    def offer(self, subnet: int, qid: int, row: np.ndarray) -> None:
        n = self.seen.get(subnet, 0) + 1
        self.seen[subnet] = n
        kept = self.rows.setdefault(subnet, [])
        if len(kept) < self.cap:
            kept.append((qid, np.array(row, copy=True)))
            return
        j = int(self.rng.integers(n))
        if j < self.cap:
            kept[j] = (qid, np.array(row, copy=True))

    def n_rows(self) -> int:
        return sum(len(v) for v in self.rows.values())


class Outcomes:
    """Outcome, latency and subnet of every query of one window."""

    def __init__(self, due: np.ndarray, slo_s: float, vocab: int,
                 acc_to_subnet: Dict[float, int], reservoir: Reservoir):
        n = len(due)
        self.due = np.asarray(due, float)       # absolute perf_counter s
        self.slo_s = float(slo_s)
        self.vocab = int(vocab)
        self.acc_to_subnet = acc_to_subnet
        self.reservoir = reservoir
        self.state = np.zeros(n, np.int8)
        self.latency = np.full(n, np.nan)       # due -> answer, s
        self.acc = np.full(n, np.nan)
        self.raised = 0
        self.bad_rows = 0
        self.unresolved = 0
        self.closed = False

    def resolve(self, i: int, t_done: float, result=None,
                error: Optional[BaseException] = None) -> None:
        """Record query ``i``'s resolution at ``t_done``; ignored once the
        window's wait has run out (the query is then already failed)."""
        if self.closed or self.state[i] != PENDING:
            return
        if error is not None:
            self.raised += 1
            self.state[i] = FAILED
            return
        row, acc = result
        if row is None:
            self.state[i] = DROPPED
            return
        row = np.asarray(row)
        if (row.shape != (self.vocab,)
                or not np.isfinite(row.astype(np.float32)).all()):
            self.bad_rows += 1
            self.state[i] = FAILED
            return
        self.state[i] = ANSWERED
        self.latency[i] = t_done - self.due[i]
        self.acc[i] = acc
        self.reservoir.offer(self.acc_to_subnet[float(acc)], i, row)

    def close(self) -> None:
        """End the wait: every query still pending has failed."""
        left = self.state == PENDING
        self.unresolved = int(left.sum())
        self.state[left] = FAILED
        self.closed = True

    # -- counts and end-to-end numbers -----------------------------------

    @property
    def attempted(self) -> int:
        return len(self.state)

    def count(self, state: int) -> int:
        return int((self.state == state).sum())

    def met(self) -> np.ndarray:
        return (self.state == ANSWERED) & (self.latency <= self.slo_s)

    def summary(self, seconds: float) -> Dict[str, float]:
        ans = self.state == ANSWERED
        lat_ms = np.sort(self.latency[ans]) * 1e3
        n = self.attempted

        def pct(q):
            return float(np.percentile(lat_ms, q)) if len(lat_ms) else None

        return {
            "attempted": n,
            "answered": int(ans.sum()),
            "dropped": self.count(DROPPED),
            "failed": self.count(FAILED),
            "raised": self.raised, "bad_rows": self.bad_rows,
            "unresolved": self.unresolved,
            "late_answers": int(ans.sum() - self.met().sum()),
            "slo_attainment": 100.0 * self.met().sum() / n if n else None,
            "goodput_qps": float(self.met().sum()) / seconds,
            "p50_latency_ms": pct(50), "p95_latency_ms": pct(95),
            "p99_latency_ms": pct(99),
            "mean_served_acc": (float(np.mean(self.acc[ans]))
                                if ans.any() else None),
            "drop_pct": 100.0 * self.count(DROPPED) / n if n else None,
        }
