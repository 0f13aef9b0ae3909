"""The comparison that decides ``correct``.

Every number compared has a limit. A check holds a number and its limit,
and whether a value passes: "max" numbers pass at or under their limit,
"min" numbers at or over it. The limits live in each configuration's file
(``check_limits``), with the readings they were set from in PERF.md.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

# Kleene truth values
F, T, U = 0, 1, 2


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    kind: str          # "max" | "min"

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value <= self.limit if self.kind == "max" else self.value >= self.limit


def checks_line(checks: Sequence[Check]) -> Dict[str, Dict]:
    return {c.name: {"value": c.value, "limit": c.limit, "pass": c.kind}
            for c in checks}


def kleene(op: str, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Three-valued ``a and b`` / ``a and not b`` / ``a or b``."""
    if op == "and_not":
        b = np.where(b == U, U, 1 - b)
        op = "and"
    out = np.full(a.shape, U, np.int8)
    if op == "and":
        out[(a == F) | (b == F)] = F
        out[(a == T) & (b == T)] = T
    elif op == "or":
        out[(a == T) | (b == T)] = T
        out[(a == F) & (b == F)] = F
    else:
        raise ValueError(op)
    return out


@dataclasses.dataclass
class LeafAnswer:
    """What the program answered for one leaf of one query."""
    pending: np.ndarray        # global doc ids the leaf decided
    scores: np.ndarray         # proxy scores over ``pending``
    labels: np.ndarray         # decisions over ``pending``
    l: float
    r: float
    truth: np.ndarray          # (N,) the generator's truth
    ref_scores: np.ndarray     # (N,) the reference's scores


@dataclasses.dataclass
class QueryAnswer:
    op: str
    mask: np.ndarray           # (N,) the query's answer
    leaves: List[LeafAnswer]   # in the predicate's order (a, b)


def f1(labels: np.ndarray, truth: np.ndarray) -> float:
    tp = float(np.sum(labels & truth))
    wrong = float(np.sum(labels != truth))
    return 2 * tp / (2 * tp + wrong) if tp + wrong else 1.0


def band_decisions(leaf: LeafAnswer, l: float, r: float) -> np.ndarray:
    """The cascade's rule: above ``r`` accept, below ``l`` reject, the
    band between them to the oracle (the truth)."""
    return np.where(leaf.scores > r, True,
                    np.where(leaf.scores < l, False, leaf.truth[leaf.pending]))


def query_readings(q: QueryAnswer) -> Dict[str, float]:
    """The readings of one query: the largest mean score gap of a leaf to
    the reference, decisions that break the cascade's rule, answers that
    differ from the composition of the leaf decisions, and the lowest F1
    of a leaf's decisions against the generator's truth (the accuracy
    target holds per leaf)."""
    gap = mism = 0.0
    f1_min = 1.0
    n = len(q.mask)
    vals = []
    for leaf in q.leaves:
        p = leaf.pending
        labels = np.asarray(leaf.labels, bool)
        if len(p):
            truth = leaf.truth[p]
            gap = max(gap, score_gap(leaf.scores, leaf.ref_scores[p]))
            mism += float(np.sum(band_decisions(leaf, leaf.l, leaf.r) != labels))
            f1_min = min(f1_min, f1(labels, truth))
        v = np.full(n, U, np.int8)
        v[p] = labels.astype(np.int8)
        vals.append(v)
    root = kleene(q.op, vals[0], vals[1])
    answer_mism = float(np.sum((root == U) | ((root == T) != np.asarray(q.mask, bool))))
    return {"score_gap": gap, "decision_mismatch": mism,
            "answer_mismatch": answer_mism, "leaf_f1_min": f1_min}


def score_gap(scores: np.ndarray, ref: np.ndarray) -> float:
    """Mean absolute gap of a leaf's scores to the reference's. The mean
    and not the widest gap: the widest of 100k documents reads 0.0013 to
    0.0024 for the program and 0.0056 to 0.0064 for the bfloat16 control
    (on one TPU v5e), too close to hold a limit between them."""
    return float(np.mean(np.abs(np.asarray(scores, np.float64) - ref)))


QUERY_KINDS = {"score_gap": "max", "decision_mismatch": "max",
               "answer_mismatch": "max", "leaf_f1_min": "min"}


def query_checks(answers: Sequence[QueryAnswer], limits: Dict[str, float]
                 ) -> List[Check]:
    """The worst reading of each number over the sampled queries."""
    return fold([query_readings(q) for q in answers], QUERY_KINDS, limits)


@dataclasses.dataclass
class TrainAnswer:
    """One proxy's training: the program's per-step losses and trained
    parameters beside the reference's, from the same initial state,
    sample and batches. Parameters are flat ``{path: array}``."""
    losses: np.ndarray          # the program's loss at each step
    final: Dict[str, np.ndarray]
    ref_losses: np.ndarray
    ref_init: Dict[str, np.ndarray]
    ref_final: Dict[str, np.ndarray]
    ref_grad0: Dict[str, np.ndarray]


def train_readings(t: TrainAnswer, first_steps: int = 3) -> Dict[str, float]:
    """``train_loss_gap``: the largest relative gap of the program's loss
    to the reference's over the first steps, where both start from the
    same state and batches. ``train_change_gap``: over the parameter
    leaves, the largest gap between the norm of the program's change and
    the reference's, against the reference's (or the median leaf's, if
    larger). Leaves whose first gradient in the reference is under a
    thousandth of the median leaf's move by round-off alone and are left
    out. ``train_param_gap`` (not compared): the largest norm of the two
    results' difference over the reference's change."""
    n = min(first_steps, len(t.ref_losses))
    if len(t.losses) < n:
        loss_gap = float("inf")
    else:
        lp, lr = np.asarray(t.losses[:n], np.float64), t.ref_losses[:n]
        loss_gap = float(np.max(np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-12)))
    g = {k: float(np.linalg.norm(v)) for k, v in t.ref_grad0.items()}
    g_med = float(np.median(list(g.values())))
    keep = [k for k in t.ref_final if g[k] >= 1e-3 * g_med]
    ref_change = {k: float(np.linalg.norm(t.ref_final[k] - t.ref_init[k])) for k in keep}
    med = float(np.median(list(ref_change.values())))
    change_gap = param_gap = 0.0
    for k in keep:
        own = float(np.linalg.norm(np.asarray(t.final[k], np.float64) - t.ref_init[k]))
        scale = max(ref_change[k], med, 1e-30)
        change_gap = max(change_gap, abs(own - ref_change[k]) / scale)
        param_gap = max(param_gap, float(np.linalg.norm(
            np.asarray(t.final[k], np.float64) - t.ref_final[k])) / scale)
    return {"train_loss_gap": loss_gap, "train_change_gap": change_gap,
            "train_param_gap": param_gap}


TRAIN_KINDS = {"train_loss_gap": "max", "train_change_gap": "max"}


def train_checks(answers: Sequence[TrainAnswer], limits: Dict[str, float]
                 ) -> List[Check]:
    return fold([train_readings(t) for t in answers], TRAIN_KINDS, limits)


def fold(readings: Sequence[Dict[str, float]], kinds: Dict[str, str],
         limits: Dict[str, float]) -> List[Check]:
    """The worst reading of each number over the sample, beside its limit."""
    out = []
    for name, kind in kinds.items():
        vals = [r[name] for r in readings]
        worst = (max(vals) if kind == "max" else min(vals)) if vals else float("nan")
        out.append(Check(name, worst, float(limits[name]), kind))
    return out


def row_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest relative L2 error of a row against the reference."""
    err = np.linalg.norm(got - ref, axis=1) / np.maximum(
        np.linalg.norm(ref, axis=1), 1e-12)
    return float(err.max()) if len(err) else float("nan")


def ingest_checks(all_rows: np.ndarray, acknowledged: int,
                  sample_rows: np.ndarray, ref: np.ndarray,
                  limits: Dict[str, float]) -> List[Check]:
    """Rows read back from the reopened store against the rows whose
    commit was acknowledged, every row finite and non-zero, and the
    sampled rows against the reference."""
    missing = float(abs(len(all_rows) - acknowledged))
    norms = np.linalg.norm(all_rows, axis=1)
    bad = float(np.sum(~np.isfinite(norms) | (norms == 0)))
    return [Check("rows_missing", missing, float(limits["rows_missing"]), "max"),
            Check("bad_rows", bad, float(limits["bad_rows"]), "max"),
            Check("row_rel_err", row_rel_err(sample_rows, ref),
                  float(limits["row_rel_err"]), "max")]
