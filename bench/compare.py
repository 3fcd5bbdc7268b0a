"""The numbers that decide ``correct``, each judged against its limit.

Training cells compare the program's first check steps with the
reference's (``bench.reference.Trainer``):

* ``loss_gap``: the widest gap between the two losses over the steps;
* ``m1_gap``: after the first step, the worst leaf's gap between the norms
  of Adam's first moment (the gradients as the optimizer got them);
* ``dparam_gap``: after the last step, the worst leaf's gap between the
  norms of the change of the parameters since the start.

A leaf gap is ``|a - b| / max(b, median leaf b)``, over the leaves whose
first reference gradient is at least a thousandth of the median leaf's:
a leaf the loss does not reach (a key bias under softmax) moves under
Adam by round-off alone, on either side.

Rollout cells compare what the served path produced with the reference
run over the same tokens:

* ``logit_rel_l2``: for slots in flight when the window closed, the
  relative L2 distance between the engine's next-token logits and the
  reference's;
* ``logp_gap``: for sampled finished sequences, the widest gap between the
  behavior log-prob the engine recorded for a served token and the
  reference's log-prob of that token. It holds for sampled tokens too,
  where the gap of a served token below the reference's best holds only
  for greedy ones.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import numpy as np

LEAF_FLOOR = 1e-3


def counted_leaves(g1_ref: Dict[str, float]) -> List[str]:
    med = statistics.median(g1_ref.values())
    return sorted(k for k, v in g1_ref.items() if v >= LEAF_FLOOR * med)


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: List[str]) -> float:
    med = statistics.median(ref[k] for k in leaves)
    worst = 0.0
    for k in leaves:
        denom = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / denom if denom > 0 else (
            0.0 if prog[k] == ref[k] else math.inf)
        worst = max(worst, gap)
    return worst


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog``/``ref``: ``losses`` per step, ``m1`` and ``dparam`` per
    leaf; ``ref`` also ``g1`` per leaf."""
    leaves = counted_leaves(ref["g1"])
    losses = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]) \
            or not all(math.isfinite(x) for x in losses):
        loss_gap = math.inf
    else:
        loss_gap = max(losses)
    return {"loss_gap": loss_gap,
            "m1_gap": leaf_gap(prog["m1"], ref["m1"], leaves),
            "dparam_gap": leaf_gap(prog["dparam"], ref["dparam"], leaves)}


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def rollout_numbers(inflight: List[Dict], served: List[Dict]
                    ) -> Dict[str, float]:
    """``inflight``: ``{"logits": engine row, "ref_logits": row}``;
    ``served``: ``{"logp": engine logps, "ref_logp": reference logps of the
    served tokens}``."""
    out = {"logit_rel_l2": max((rel_l2(s["logits"], s["ref_logits"])
                                for s in inflight), default=math.inf)}
    gaps = [np.max(np.abs(np.asarray(s["logp"], np.float64)
                          - np.asarray(s["ref_logp"], np.float64)))
            for s in served if len(s["logp"])]
    out["logp_gap"] = float(max(gaps)) if gaps else math.inf
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every number the cell's limits name, beside its limit; correct when
    each is finite and at or under it."""
    checks: Dict[str, Dict[str, float]] = {}
    ok = True
    for name, limit in limits.items():
        v = numbers.get(name, math.inf)
        v = float(v) if v is not None else math.inf
        checks[name] = {"value": v, "limit": float(limit)}
        ok &= math.isfinite(v) and v <= limit
    return ok, checks


def describe(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k}: {c['value']!r} limit {c['limit']!r}"
            for k, c in checks.items()]

