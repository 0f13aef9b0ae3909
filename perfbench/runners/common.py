"""What every runner shares: the run's inputs, what a measurement hands
back, and the query cells' engine, executor and checks."""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from perfbench import data
from perfbench.checks import Check, LeafAnswer, QueryAnswer, TrainAnswer
from perfbench.devtrace import DeviceTrace
from perfbench.readings import Readings


@dataclasses.dataclass
class Run:
    workload: str
    config: Dict
    traffic: Dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    t0: float                   # host clock at process start
    workdir: Path               # fresh working directory inside the checkout
    peak: Dict                  # the peak table's row for this chip

    def device_trace(self) -> Optional[DeviceTrace]:
        return DeviceTrace(self.workdir / "trace") if self.trace else None


@dataclasses.dataclass
class Measured:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    readings: Optional[Readings]
    # runs after the window has closed and the memory peak is read: frees
    # the program's state, then compares with the reference
    check: Callable[[], List[Check]]
    # the control's and the planted faults' readings of the same numbers
    # (perfbench/control.py; a benchmark run never calls it)
    control: Optional[Callable[[], Dict[str, float]]] = None


def log(msg: str) -> None:
    import sys
    print(f"[perfbench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# query cells
# ---------------------------------------------------------------------------

def engine_for(cfg: Dict, embeds: np.ndarray, executor_cls):
    """The program's engine over the store, at the configuration's proxy
    and cascade settings, with a recording executor."""
    from repro.config.base import CascadeConfig, ProxyConfig
    from repro.engine import InMemoryStore, ScaleDocEngine
    fields = set(ProxyConfig.__dataclass_fields__)
    proxy = ProxyConfig(**{k: v for k, v in cfg["proxy"].items() if k in fields})
    cascade = CascadeConfig(**cfg["cascade"])
    ex = executor_cls(chunk=cfg["chunk"])
    return ScaleDocEngine(InMemoryStore(embeds), proxy, cascade,
                          chunk=cfg["chunk"], executor=ex), ex


def recording_executor():
    """A ScoringExecutor that keeps, per query embedding, the trained
    proxy it scored with: the reference scores with the same proxy."""
    from repro.engine import ScoringExecutor

    class RecordingExecutor(ScoringExecutor):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.proxies: Dict[bytes, Dict] = {}

        def score(self, params, e_q, store):
            if params is not None:
                self.proxies[np.asarray(e_q, np.float32).tobytes()] = params
            return super().score(params, e_q, store)

    return RecordingExecutor


def compose(op: str, a, b):
    return {"and": lambda: a & b, "and_not": lambda: a & ~b,
            "or": lambda: a | b}[op]()


def predicate(leaf: data.Leaf, oracle, name: str):
    from repro.engine import SemanticPredicate
    return SemanticPredicate(leaf.embed, oracle, name=name)


@dataclasses.dataclass
class Answered:
    """One finished query, kept for the check."""
    op: str
    leaves: List[data.Leaf]
    keys: List[str]             # the predicates' leaf keys, in (a, b) order
    result: object              # FilterResult


def query_answers(answered: List[Answered], executor, embeds: np.ndarray,
                  precision: str = "float32") -> List[QueryAnswer]:
    """Pair each query's leaf reports with the reference's scores, which
    are computed once per distinct leaf over the whole store."""
    import jax
    from perfbench.reference import proxy as ref
    docs = jax.device_put(embeds)
    cache: Dict[bytes, np.ndarray] = {}
    out = []
    for q in answered:
        reports = {rep.key: rep for rep in q.result.leaf_reports}
        leaves = []
        for leaf, key in zip(q.leaves, q.keys):
            rep = reports[key]
            k = leaf.embed.tobytes()
            if k not in cache:
                cache[k] = ref.scores(ref.layers_of(executor.proxies[k]),
                                      leaf.embed, docs, precision)
            leaves.append(LeafAnswer(
                pending=np.asarray(rep.pending, np.int64),
                scores=np.asarray(rep.scores, np.float32),
                labels=np.asarray(rep.labels, bool),
                l=float(rep.cascade.l), r=float(rep.cascade.r),
                truth=leaf.truth, ref_scores=cache[k]))
        out.append(QueryAnswer(op=q.op, mask=np.asarray(q.result.mask, bool),
                               leaves=leaves))
    del docs
    return out


class TrainerRecorder:
    """Keeps each proxy training's per-step losses, by query embedding, as
    the program's trainer hands them back to the engine. Installed around
    the engine's ``train_proxy_multi`` for the run; ``close`` puts the
    trainer back."""

    def __init__(self):
        import repro.engine.engine as eng
        self._eng, self._real = eng, eng.train_proxy_multi
        self.losses: Dict[bytes, np.ndarray] = {}

        def train(keys, e_qs, samples, labels, cfg):
            res = self._real(keys, e_qs, samples, labels, cfg)
            e = np.asarray(e_qs, np.float32)
            for i in range(len(samples)):
                self.losses[e[i].tobytes()] = np.concatenate(
                    [res.phase1_losses[i], res.phase2_losses[i]])
            return res

        eng.train_proxy_multi = train

    def close(self) -> None:
        self._eng.train_proxy_multi = self._real


def flat(tree, prefix: str = "") -> Dict[str, np.ndarray]:
    """``{"layers.l0.w": array, ..., "proj.b": array}`` in float64."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = np.asarray(v, np.float64)
    return out


def train_check_leaves(answered: List[Answered], count: int, seed: int,
                       last: Callable[[data.Leaf], bool] = lambda leaf: False
                       ) -> List[data.Leaf]:
    """``count`` distinct leaves of the answered queries in an order drawn
    from the seed, the leaves that ``last`` names after the others."""
    leaves = {}
    for q in answered:
        for leaf in q.leaves:
            leaves.setdefault(leaf.embed.tobytes(), leaf)
    pool = list(leaves.values())
    order = np.random.default_rng(np.random.SeedSequence([seed, 19])).permutation(len(pool))
    return sorted((pool[k] for k in order), key=last)[:count]


def train_answers(leaves: List[data.Leaf], executor, recorder: TrainerRecorder,
                  embeds: np.ndarray, cfg: Dict, pseed: int, **variant
                  ) -> List[TrainAnswer]:
    """The program's training of each leaf beside the float32 reference's,
    retrained from the leaf's key and labelled sample. ``variant`` (a
    ``dtype``, ``batch_size`` or ``steps`` of the reference) puts a
    control or a planted fault in the program's place."""
    from perfbench.reference import trainer as ref
    proxy = cfg["proxy"]
    out = []
    for leaf in leaves:
        idx, key = ref.sample(pseed, leaf.embed, len(embeds), proxy["train_fraction"])
        want = ref.train(key, leaf.embed, embeds[idx], leaf.truth[idx], proxy)
        k = leaf.embed.tobytes()
        if variant:
            got = ref.train(key, leaf.embed, embeds[idx], leaf.truth[idx], proxy, **variant)
            losses, final = got.losses, got.final
        else:
            losses, final = recorder.losses.get(k, np.zeros(0)), executor.proxies[k]
        out.append(TrainAnswer(losses=np.asarray(losses), final=flat(final),
                               ref_losses=want.losses, ref_init=flat(want.init),
                               ref_final=flat(want.final), ref_grad0=flat(want.grad0)))
    return out


def query_control(answered: List[Answered], executor, embeds: np.ndarray,
                  train_leaves: List[data.Leaf] = (), cfg: Dict = None,
                  pseed: int = 0) -> Dict[str, float]:
    """What the checks read for the control and the planted faults.

    ``control.score_gap``: the reference in bfloat16, in the program's
    place, against the float32 reference, over the same leaves and
    documents (the largest mean gap of a leaf, as the check reads it).
    ``fault.no_band_f1`` and ``fault.half_band_f1``: the lowest leaf F1
    when the cascade's band is cut to its midpoint or to half its width
    about it, as a calibration that narrows it would.
    ``control.train_*`` / ``fault.half_batch.train_*`` /
    ``fault.fewer_steps.train_*``: the trainer's numbers for the
    reference trained in bfloat16, on half of each batch, and for half
    the steps of each phase, in the program's place."""
    from perfbench import checks as chk
    f32 = query_answers(answered, executor, embeds, "float32")
    bf16 = query_answers(answered, executor, embeds, "bfloat16")
    gap, no_band, half_band = 0.0, 1.0, 1.0
    for q32, q16, q in zip(f32, bf16, answered):
        for a, b, leaf in zip(q32.leaves, q16.leaves, q.leaves):
            p = a.pending
            if not len(p):
                continue
            gap = max(gap, chk.score_gap(b.ref_scores[p], a.ref_scores[p]))
            mid, half = (a.l + a.r) / 2, (a.r - a.l) / 4
            truth = leaf.truth[p]
            no_band = min(no_band, chk.f1(chk.band_decisions(a, mid, mid), truth))
            half_band = min(half_band, chk.f1(
                chk.band_decisions(a, mid - half, mid + half), truth))
    out = {"control.score_gap": gap, "fault.no_band_f1": no_band,
           "fault.half_band_f1": half_band}
    if train_leaves:
        proxy = cfg["proxy"]
        variants = {"control": dict(dtype="bfloat16"),
                    "fault.half_batch": dict(batch_size=proxy["batch_size"] // 2),
                    "fault.fewer_steps": dict(steps=(proxy["phase1_steps"] // 2,
                                                     proxy["phase2_steps"] // 2))}
        for name, variant in variants.items():
            ts = train_answers(train_leaves, executor, None, embeds, cfg, pseed, **variant)
            for key in ("train_loss_gap", "train_change_gap", "train_param_gap"):
                out[f"{name}.{key}"] = max(chk.train_readings(t)[key] for t in ts)
    return out
