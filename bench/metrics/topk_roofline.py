"""Share of the bytes-bound roofline reached by the top-k: the least time
the op's shapes allow (input logits read once, k values and int32 indices
written, over peak HBM bandwidth; ``bench/work.py``) over the device time
the trace gives it in the traced slice.

* Sampler cell: the device time of everything inside the ``bench.call``
  annotations, the whole sampling call (the gather ``segment_topk`` runs
  before its kernels, the kernels, the draw), over as many calls as the
  slice holds, each counted by the share of it inside the slice.
* Serving cells: the device time of the top-k Pallas kernels inside
  ``sched.decode``. The trace names the decode program's XLA ops only by
  HLO instruction, so the gather before the kernels is not counted there;
  ``mfu.batch`` bounds the whole step."""
import re

from bench import work

#: the Pallas kernels that implement the top-k, by the name the trace gives them
TOPK_KERNELS = re.compile(r"^(vocab_topk|router_topk|segment_class_(sort|merge))_pallas(\.\d+)?$")


def kernel_time(ops: dict) -> float:
    return sum(t for name, t in ops.items() if TOPK_KERNELS.search(name))


def read(rec: dict, name: str):
    tr = rec.get("trace")
    if not tr:
        return None
    bw = rec["peaks"]["hbm_bytes_per_s"]
    if "least_time_s_per_call" in rec:  # sampler cell: the whole call
        t = sum(tr["ops_in"].get("bench.call", {}).values())
        least = rec["least_time_s_per_call"] * tr.get("call_share", 0.0)
    else:  # serving cells: one top-k per decode step
        t = kernel_time(tr["ops_in"].get("sched.decode", {}))
        rows = {idx: n for idx, _, _, n in rec["steps"]}
        least = sum(work.topk_bytes(rows[s], rec["vocab"], [rec["k"]] * rows[s])
                    for s in tr["steps"] if rows.get(s)) / bw
    if t <= 0 or least <= 0:
        return None
    return 100.0 * least / t
