"""C4/C5 observability: telemetry prints, structured per-iteration records,
per-rank CSV dumps (parity with reference ``example/main.py:33,76-105``) —
and, since ISSUE 12, the fleet's ONE metrics registry.

Log record schema matches the reference exactly: ``timestamp, iteration,
training_loss`` every step, plus ``test_loss, test_accuracy`` on eval
iterations (``example/main.py:76-84``); CSVs are written with an ``index``
label column via pandas (``:97-105``).

Registry (ISSUE 12): EWMAs and counters used to be hand-rolled across ~12
modules — the ``x if e == 0.0 else 0.7*e + 0.3*x`` idiom in
``parallel/sharded_ps.py`` (step latency, loss, grad norm),
``parallel/mpmd.py`` (per-stage busy ms), the winsorized mean/variance in
``utils/health.py``, plus a dozen ``stats`` dicts. The decay constants and
the winsorization now live HERE (:class:`Ewma`, :class:`EwmaMeanVar` —
bit-identical update rules, regression-pinned against the LeaseRenew float
layout in ``tests/test_obs.py``), and :class:`Registry` gives one
``snapshot()`` JSON over owned metrics plus *attached* providers (existing
``stats`` dicts register lazily — no rewrite needed to be visible).
``--metrics-dump`` on the training/serving/coord CLIs and the
``fleet_metrics`` tail on FleetState read from this registry.
"""

from __future__ import annotations

import json
import os
import threading
from datetime import datetime
from typing import Callable, Dict, List, Optional

import numpy as np

#: THE fleet telemetry decay constant (the 0.7/0.3 idiom every plane used):
#: one place, so per-module drift (ISSUE 12 satellite) is structurally gone.
TELEMETRY_ALPHA = 0.3


class Counter:
    """Monotonic event counter (GIL-atomic ``+=`` — same discipline as the
    transport ``stats`` dicts it unifies)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1) -> int:
        self.value += int(n)
        return self.value


class Gauge:
    """Last-written value."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> float:
        self.value = float(v)
        return self.value


class Ewma:
    """The fleet's EWMA, bit-identical to the hand-rolled idiom it
    replaces: ``x`` seeds on the first sample (legacy sentinel: a value of
    exactly 0.0 reads as unset), then ``value = (1-alpha)*value +
    alpha*x``. With the default alpha, ``1.0 - 0.3 == 0.7`` exactly in
    IEEE double, so migrated LeaseRenew telemetry stays byte-identical on
    the wire (regression-tested)."""

    __slots__ = ("alpha", "value")

    def __init__(self, alpha: float = TELEMETRY_ALPHA):
        if not 0 < alpha <= 1:
            raise ValueError(f"need 0 < alpha <= 1, got {alpha}")
        self.alpha = float(alpha)
        self.value = 0.0

    def update(self, x: float) -> float:
        x = float(x)
        self.value = (x if self.value == 0.0
                      else (1.0 - self.alpha) * self.value + self.alpha * x)
        return self.value

    def reset(self) -> None:
        self.value = 0.0


class EwmaMeanVar:
    """EWMA mean + variance with optional winsorized folding — the
    admission gate's statistics (``utils/health.py``), verbatim: rejected
    samples are never folded (caller's choice), and an ADMITTED sample may
    be clamped at ``winsor`` before it moves the mean (the boiling-frog
    defense: a ramp of just-under-threshold outliers must not walk the
    gate up an exponential)."""

    __slots__ = ("alpha", "mean", "var", "count")

    def __init__(self, alpha: float = 0.2):
        if not 0 < alpha <= 1:
            raise ValueError(f"need 0 < alpha <= 1, got {alpha}")
        self.alpha = float(alpha)
        self.mean = 0.0
        self.var = 0.0
        self.count = 0

    def sigma(self, floor: float = 0.0) -> float:
        import math

        return max(math.sqrt(max(self.var, 0.0)), float(floor))

    def zscore(self, x: float, sigma_floor: float = 0.0) -> float:
        return (float(x) - self.mean) / self.sigma(sigma_floor)

    def update(self, x: float, winsor: Optional[float] = None) -> None:
        x = float(x)
        if self.count == 0:
            self.mean = x
            self.var = 0.0
        else:
            d = x - self.mean
            if winsor is not None:
                d = max(-winsor, min(winsor, d))
            self.mean += self.alpha * d
            self.var = (1.0 - self.alpha) * (self.var + self.alpha * d * d)
        self.count += 1


class Registry:
    """One named home for a process's metrics.

    Owned metrics (:meth:`counter` / :meth:`gauge` / :meth:`ewma`) are
    get-or-create by name; a name can hold exactly one kind (a kind clash
    raises — two modules silently sharing a name under different
    semantics is the drift this registry exists to kill). *Attached
    providers* (:meth:`attach`) are zero-cost adapters over the stats
    dicts the codebase already keeps: a callable returning a flat dict,
    sampled lazily at :meth:`snapshot` under the provider's own
    ``prefix.`` namespace (a provider that raises is reported as
    ``{prefix}.error`` instead of killing the dump)."""

    def __init__(self, name: str = ""):
        self.name = str(name)
        self._mu = threading.Lock()
        self._metrics: Dict[str, object] = {}
        self._providers: Dict[str, Callable[[], dict]] = {}

    def _get(self, name: str, cls, factory=None):
        with self._mu:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = (factory or cls)()
            elif not isinstance(m, cls):
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, wanted {cls.__name__}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def ewma(self, name: str, alpha: float = TELEMETRY_ALPHA) -> Ewma:
        m = self._get(name, Ewma, factory=lambda: Ewma(alpha))
        if m.alpha != float(alpha):
            # two modules silently sharing one name under different decay
            # rates is the drift this registry exists to kill
            raise ValueError(
                f"ewma {name!r} already registered with alpha={m.alpha}, "
                f"requested {alpha}")
        return m

    def attach(self, prefix: str, provider: Callable[[], dict]) -> None:
        """Register a lazy stats provider under ``prefix.`` (replacing any
        previous provider of the same prefix — a restarted component
        re-attaches its new self)."""
        with self._mu:
            self._providers[str(prefix)] = provider

    def detach(self, prefix: str) -> None:
        with self._mu:
            self._providers.pop(str(prefix), None)

    def snapshot(self) -> Dict[str, object]:
        """Flat ``name -> value`` JSON-ready dict over owned metrics and
        every attached provider."""
        with self._mu:
            metrics = dict(self._metrics)
            providers = dict(self._providers)
        out: Dict[str, object] = {}
        for name, m in sorted(metrics.items()):
            if isinstance(m, EwmaMeanVar):
                out[name] = {"mean": m.mean, "var": m.var, "count": m.count}
            else:
                out[name] = m.value
        for prefix, provider in sorted(providers.items()):
            try:
                stats = provider()
            except Exception as e:  # noqa: BLE001 — a dump must not die
                out[f"{prefix}.error"] = repr(e)
                continue
            for k, v in sorted(dict(stats).items()):
                out[f"{prefix}.{k}"] = v

        return out

    def dump_json(self, path: Optional[str] = None) -> str:
        """Serialize :meth:`snapshot` (and write it to ``path`` when
        given) — the ``--metrics-dump`` implementation."""
        text = json.dumps(self.snapshot(), indent=2, sort_keys=True,
                          default=str)
        if path:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(path, "w") as fh:
                fh.write(text + "\n")
        return text


_DEFAULT_REGISTRY = Registry("default")


def get_registry() -> Registry:
    """The process-default registry (CLIs dump this one)."""
    return _DEFAULT_REGISTRY


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile of a 1-D sample (``q`` in [0, 100]).

    The serving SLO reporter's primitive (TTFT/TPOT summaries,
    ``serving/engine.py``). A thin, loud wrapper
    over ``np.percentile``: empty samples and out-of-range ``q`` raise
    instead of returning NaN — an SLO line with a silent NaN percentile is
    worse than a crash.
    """
    arr = np.asarray(list(values), np.float64)
    if arr.size == 0:
        raise ValueError("percentile() of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    return float(np.percentile(arr, q))


def latency_summary(values, percentiles=(50, 90, 99)) -> Optional[Dict]:
    """Summary dict over a latency sample: count/mean/max plus the given
    percentiles (keys ``p50`` etc.). Returns ``None`` for an empty sample so
    callers can print "n/a" instead of fabricating numbers."""
    arr = np.asarray(list(values), np.float64)
    if arr.size == 0:
        return None
    out = {
        "count": int(arr.size),
        "mean": float(arr.mean()),
        "max": float(arr.max()),
    }
    for q in percentiles:
        out[f"p{q:g}"] = percentile(arr, q)
    return out


class MetricsLogger:
    """Accumulates per-iteration log records and dumps one CSV per rank."""

    def __init__(self, log_dir: str = "log"):
        self.log_dir = log_dir
        self.records: List[Dict] = []

    def log_step(self, iteration: int, training_loss: float, **extra) -> Dict:
        rec = {
            "timestamp": datetime.now(),
            "iteration": iteration,
            "training_loss": float(training_loss),
        }
        rec.update(extra)
        self.records.append(rec)
        return rec

    def to_csv(self, filename: str) -> str:
        """Dump accumulated records (reference ``example/main.py:97-105``).

        ``filename`` examples: ``single.csv``, ``tpu.csv`` (the reference's
        ``gpu.csv`` renamed for this hardware), ``node{rank}.csv``.
        """
        import pandas as pd

        os.makedirs(self.log_dir, exist_ok=True)
        path = os.path.join(self.log_dir, filename)
        df = pd.DataFrame(self.records)
        df.to_csv(path, index_label="index")
        return path


def print_eval_line(rec: Dict) -> None:
    """Per-interval telemetry line (format parity with ``example/main.py:85-89``)."""
    print(
        "Timestamp: {timestamp} | "
        "Iteration: {iteration:6} | "
        "Loss: {training_loss:6.4f} | "
        "Test Loss: {test_loss:6.4f} | "
        "Test Accuracy: {test_accuracy:6.4f}".format(**rec)
    )


def print_classification_report(
    y_true: np.ndarray, y_pred: np.ndarray, class_names, test_loss: float, accuracy: float
) -> None:
    """Verbose per-epoch eval report (reference ``example/main.py:128-131``).

    Unlike the reference — which scores only the final test batch and passes
    ``(predicted, labels)`` to sklearn in swapped order (a defect SURVEY.md §7
    says not to copy) — this reports over the full test set with ``y_true``
    first.
    """
    from sklearn.metrics import classification_report

    print("Loss: {:.3f}".format(test_loss))
    print("Accuracy: {:.3f}".format(accuracy))
    print(
        classification_report(
            np.asarray(y_true), np.asarray(y_pred), target_names=list(class_names),
            labels=list(range(len(class_names))), zero_division=0,
        )
    )
