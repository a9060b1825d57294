"""Host time per executor call (ms), measured inside the call: the mean
over the traced slice of the program's ``worker.run`` span minus its
``executor.device_wait`` child (stacking, padding, launch, the logits
copy and the slice). Moves ``p95_latency_ms``."""
from chipbench import program_spans


def read(ctx):
    recs = program_spans.in_slice(ctx)
    if recs is None:
        return None
    runs = {s.sid: s.t1 - s.t0 for s in recs if s.name == "worker.run"}
    if not runs:
        return None
    for s in recs:
        if s.name == "executor.device_wait" and s.parent in runs:
            runs[s.parent] -= s.t1 - s.t0
    return sum(runs.values()) / len(runs) * 1e3
