"""Measured stage-split tuner: compile-cost curve vs boundary tax.

Replaces the hardcoded ``maxStageOps=20`` auto-split. Compile time grows
superlinearly with the size of a fused stage, but splitting trades compile
seconds against a REAL per-boundary cost — every extra stage boundary
pays a dispatch + D2H/H2D round trip — and the right cut point is a property
of the platform, not a constant. SystemML's fusion-plan work (PAPERS:
arXiv:1801.00829) and FusionStitching (arXiv:1811.05213) both cost this
granularity tradeoff explicitly; this module does the same with numbers
measured on THIS machine:

  * every actual stage compile (exec/compilequeue.py) records
    (op count, seconds) into a per-platform JSON model persisted under the
    cache dir — the compile-seconds-vs-op-count curve is FIT (power law,
    log-log least squares) once enough distinct sizes accumulate. Until
    then XLA:CPU predicts from a default anchored on its observed
    zillow/flights compiles; a platform nobody has observed has NO curve,
    keeps its stages fused and degrades nothing;
  * the first device dispatch of every boundary-fed stage (exec/local.py)
    records the measured per-boundary dispatch cost;
  * ``plan_split`` picks the segment count k minimizing
    predicted_compile(k) + (k-1) * boundary_cost, subject to the
    ``tuplex.tpu.compileBudgetS`` ceiling — and when even the finest split
    blows the budget, degrades the stage to a host-CPU compile with device
    transfer (the stage still runs, just without an accelerator kernel).

The decision (prediction + chosen split) is logged at plan time and recorded
on the stage for metrics/history/compilestats.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

# default power-law curve t(n) = a + b * n^c for XLA:CPU, anchored on measured
# compiles: zillow's 13-op stage compiles in ~40 s locally but flights' 43-op
# stage ran >20 min at >120 GB RSS before being killed (c >= ln(30)/ln(3.3)
# ~= 2.9 between those two anchors — the barrier-laden mega-fusions blow up
# XLA:CPU superlinearly). No other platform gets a default: a curve nobody
# measured on the device would shape its plans.
_DEFAULT_CURVE = {"cpu": (0.3, 0.05, 2.5)}
_DEFAULT_BOUNDARY = {"cpu": 0.005}

_MAX_OBS = 256          # persisted observation window per platform


def _model_dir() -> str:
    from ..runtime.jaxcfg import state_dir

    d = os.environ.get("TUPLEX_COMPILE_MODEL_DIR", "") \
        or state_dir("compile_model")
    try:
        os.makedirs(d, exist_ok=True)
    except OSError:
        return ""
    return d


class CompileModel:
    """Per-platform compile-time model: raw (op count, seconds) observations
    plus per-boundary dispatch samples, persisted as JSON; predictions come
    from a power-law fit when >=3 distinct op counts are on record, else
    from the default curve — `default_curve`/`default_boundary` when given,
    else the platform's own (only XLA:CPU has one). With neither a fit
    nor a default the model predicts nothing (``curve()[0] is None``) and
    ``plan_split`` keeps the stage fused."""

    def __init__(self, platform: str, path: Optional[str] = None,
                 default_curve: Optional[tuple] = None,
                 default_boundary: Optional[float] = None):
        self.platform = platform
        self._default = default_curve if default_curve is not None \
            else _DEFAULT_CURVE.get(platform)
        self._default_boundary = default_boundary \
            if default_boundary is not None \
            else _DEFAULT_BOUNDARY.get(platform, 0.0)
        d = _model_dir()
        self.path = path if path is not None else (
            os.path.join(d, f"compile_model_{platform}.json") if d else "")
        self.obs: list[list] = []        # [n_ops, seconds]
        # census-tagged observations [families dict, seconds] recorded by
        # the compile queue when graphlint is on: the raw material for the
        # per-family compile-cost terms (family_weights) that ride
        # ALONGSIDE the op-count power law in predict()
        self.fam_obs: list[list] = []
        self.boundary: list[float] = []
        # measured warm per-dispatch DEVICE seconds (runtime/devprof:
        # launch→ready, compile excluded) — the first real device-cost
        # feature in the split decision: an extra boundary re-dispatches
        # the downstream segment, so its measured device occupancy joins
        # the host-side boundary tax below
        self.device: list[float] = []
        # n_ops -> best-known LOWER BOUND seconds for compiles that have
        # not (yet) finished: a watchdog in the compile queue refreshes
        # this while a compile runs, so a compile that is killed /
        # wedges forever still teaches the model — without this, the
        # catastrophic compiles are exactly the ones the observation set
        # never contains (survivorship bias), and the fit extrapolated
        # from small finished compiles keeps predicting they are fine
        self.censored: dict[int, float] = {}
        self._fit: Optional[tuple] = None
        self._fam_fit: Optional[tuple] = None
        self._lock = threading.Lock()
        self._load()

    # -- persistence ----------------------------------------------------
    def _load(self) -> None:
        if not self.path or not os.path.exists(self.path):
            return
        try:
            with open(self.path) as fp:
                d = json.load(fp)
            self.obs = [o for o in d.get("obs", [])
                        if isinstance(o, list) and len(o) == 2][-_MAX_OBS:]
            self.fam_obs = [o for o in d.get("fam_obs", [])
                            if isinstance(o, list) and len(o) == 2
                            and isinstance(o[0], dict)][-_MAX_OBS:]
            self.boundary = [float(b) for b in
                             d.get("boundary", [])][-_MAX_OBS:]
            self.device = [float(b) for b in
                           d.get("device", [])][-_MAX_OBS:]
            self.censored = {int(k): float(v) for k, v in
                             d.get("censored", {}).items()}
        except Exception:   # pragma: no cover - corrupt model: start fresh
            self.obs, self.boundary, self.censored = [], [], {}
            self.device, self.fam_obs = [], []
        self._fit = None
        self._fam_fit = None

    def _save(self) -> None:
        if not self.path:
            return
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as fp:
                json.dump({"platform": self.platform, "updated": time.time(),
                           "obs": self.obs[-_MAX_OBS:],
                           "fam_obs": self.fam_obs[-_MAX_OBS:],
                           "boundary": self.boundary[-_MAX_OBS:],
                           "device": self.device[-_MAX_OBS:],
                           "censored": {str(k): v for k, v in
                                        self.censored.items()}}, fp)
            os.replace(tmp, self.path)
        except OSError:   # pragma: no cover - model persistence best-effort
            pass

    # -- recording ------------------------------------------------------
    def record_compile(self, n_ops: int, seconds: float,
                       families: Optional[dict] = None) -> None:
        if n_ops <= 0 or seconds <= 0:
            return
        with self._lock:
            self.obs.append([int(n_ops), float(seconds)])
            self.obs = self.obs[-_MAX_OBS:]
            if families:
                self.fam_obs.append([
                    {str(k): int(v) for k, v in families.items() if v},
                    float(seconds)])
                self.fam_obs = self.fam_obs[-_MAX_OBS:]
                self._fam_fit = None
            self._fit = None
            self._save()

    def record_running(self, n_ops: int, seconds_so_far: float) -> None:
        """Censored observation: a compile of `n_ops` has been running
        for `seconds_so_far` and is not done. Keeps the best lower bound
        per size; survives the process being killed mid-compile."""
        if n_ops <= 0 or seconds_so_far <= 0:
            return
        with self._lock:
            if seconds_so_far > self.censored.get(int(n_ops), 0.0):
                self.censored[int(n_ops)] = float(seconds_so_far)
                self._fit = None
                self._save()

    def record_boundary(self, seconds: float) -> None:
        if seconds <= 0:
            return
        with self._lock:
            self.boundary.append(float(seconds))
            self.boundary = self.boundary[-_MAX_OBS:]
            self._save()

    def record_device_dispatch(self, seconds: float) -> None:
        """Measured warm device seconds for one stage dispatch (devprof
        feeds the per-stage warm MEDIAN once per stage per process, so
        one chatty stage can't flood the window)."""
        if seconds <= 0:
            return
        with self._lock:
            self.device.append(float(seconds))
            self.device = self.device[-_MAX_OBS:]
            self._save()

    # -- prediction -----------------------------------------------------
    def curve(self) -> tuple[Optional[tuple], bool]:
        """((a, b, c), fitted?) for t(n) = a + b * n^c; (None, False) for
        a platform with neither observations nor a default. The fit is a
        2-parameter log-log least squares over per-size medians (the fixed
        term a is dropped once real data exists — it is inside the
        measurements), with censored lower-bound points (compiles that
        never finished) included as regular observations; the exponent
        clamps to [0.8, 3.0] so a couple of noisy points can't produce an
        absurd extrapolation."""
        with self._lock:
            if self._fit is not None:
                return self._fit
            by_n: dict[int, list[float]] = {}
            for n, s in self.obs:
                by_n.setdefault(int(n), []).append(float(s))
            max_done = max(by_n, default=0)
            for n, s in self.censored.items():
                # censored lower bounds join the fit only ABOVE the
                # finished-compile range: that is where survivorship bias
                # lives (big fused stages that never finish). A wedge at
                # a SMALL op count (XLA choking on one pathological fn
                # shape, not on size) must not bend the whole curve —
                # the per-fingerprint deadline marker handles those
                # (exec/compilequeue CompileTimeout negative cache).
                if int(n) > max_done and s > max(by_n.get(int(n), [0.0])):
                    by_n.setdefault(int(n), []).append(float(s))
            if len(by_n) >= 3:
                xs, ys = [], []
                for n, ss in by_n.items():
                    ss = sorted(ss)
                    med = ss[len(ss) // 2]
                    xs.append(math.log(max(n, 1)))
                    ys.append(math.log(max(med, 1e-4)))
                k = len(xs)
                mx, my = sum(xs) / k, sum(ys) / k
                den = sum((x - mx) ** 2 for x in xs)
                if den > 1e-9:
                    c = sum((x - mx) * (y - my)
                            for x, y in zip(xs, ys)) / den
                    c = min(3.0, max(0.8, c))
                    b = math.exp(my - c * mx)
                    self._fit = ((0.0, b, c), True)
                    return self._fit
            self._fit = (self._default, False)
            return self._fit

    def _max_observed_n(self) -> int:
        n = max((int(o[0]) for o in self.obs), default=0)
        return max(n, max(self.censored, default=0))

    def predict(self, n_ops: int) -> float:
        """Predicted compile seconds for a fused stage of `n_ops`
        operators. Beyond 1.5x the largest size ever observed the
        prediction never drops below the platform DEFAULT curve: a fit
        over small finished compiles must not extrapolate a regime change
        away (XLA's blowup on mega-fusions starts where the observations
        stop, precisely because those compiles don't finish)."""
        n_ops = max(int(n_ops), 1)
        curve, fitted = self.curve()
        if curve is None:
            return 0.0
        a, b, c = curve
        pred = a + b * n_ops ** c
        if fitted and self._default is not None \
                and n_ops > 1.5 * max(self._max_observed_n(), 1):
            da, db, dc = self._default
            pred = max(pred, da + db * n_ops ** dc)
        # hard floor at censored lower bounds (compile time is monotone in
        # op count): a least-squares fit may pass BELOW a lower-bound
        # point. Same above-the-finished-range scoping as the fit.
        with self._lock:
            max_done = max((int(o[0]) for o in self.obs), default=0)
            for cn, cs in self.censored.items():
                if cn > max_done and n_ops >= cn:
                    pred = max(pred, cs)
        return pred

    # -- per-family construct terms (graphlint census) ------------------
    def family_weights(self) -> tuple[dict, bool]:
        """(per-family compile-seconds weights, fitted?). Fitted by ridge
        least squares over census-tagged compile observations (each one a
        primitive-family count vector from compiler/graphlint paired with
        the measured compile seconds) once >=6 are on record; before
        that, the graphlint seed weights calibrated offline against the
        bundled-pipeline corpus. Weights clamp non-negative — a family
        can't make a compile FASTER, and a noisy fit must not let e.g.
        scatters subsidize elementwise ops."""
        from ..compiler import graphlint as GL

        with self._lock:
            if self._fam_fit is not None:
                return self._fam_fit
            obs = list(self.fam_obs)
        fams = sorted({f for fam, _ in obs for f in fam})
        if len(obs) >= 6 and fams:
            try:
                import numpy as np

                A = np.array([[float(fam.get(f, 0)) for f in fams]
                              for fam, _ in obs])
                y = np.array([float(s) for _, s in obs])
                lam = 1e-3 * max(float((A * A).sum()), 1.0) / A.shape[1]
                w = np.linalg.solve(A.T @ A + lam * np.eye(len(fams)),
                                    A.T @ y)
                weights = dict(GL.FAMILY_WEIGHTS)
                for f, wf in zip(fams, w):
                    weights[f] = max(float(wf), 0.0)
                with self._lock:
                    self._fam_fit = (weights, True)
                return self._fam_fit
            except Exception:   # pragma: no cover - singular/odd census
                pass
        with self._lock:
            self._fam_fit = (dict(GL.FAMILY_WEIGHTS), False)
            return self._fam_fit

    def census_cost(self, families: dict) -> float:
        """Predicted compile seconds from the construct census alone:
        sum of per-family weights times counts. Rides ALONGSIDE the
        op-count power law in plan_split — two scatter-heavy ops can cost
        what twenty elementwise ops do, which op count can't see."""
        w, _ = self.family_weights()
        return sum(w.get(f, 0.0) * float(c) for f, c in families.items())

    def boundary_cost(self) -> float:
        """Measured per-boundary dispatch+transfer tax (median), or the
        default before any boundary has been observed."""
        with self._lock:
            if self.boundary:
                b = sorted(self.boundary)
                return b[len(b) // 2]
        return self._default_boundary

    def device_dispatch_cost(self) -> float:
        """The FIXED device-side cost of one extra dispatch, estimated
        as the smallest measured warm dispatch (runtime/devprof feeds
        per-stage warm medians); 0.0 before any measurement exists.
        Minimum, not median: a stage's occupancy is mostly compute that
        SPLITS with the stage — only the fixed part (launch, output
        round-trip, lost-fusion floor) is paid per extra boundary, and
        the cheapest observed dispatch is the best available proxy for
        it (an upper bound that tightens as small dispatches are
        observed)."""
        with self._lock:
            if self.device:
                return min(self.device)
        return 0.0


_MODELS: dict[str, CompileModel] = {}
_MODELS_LOCK = threading.Lock()


def model_for(platform: Optional[str] = None) -> CompileModel:
    if platform is None:
        from ..runtime.jaxcfg import jax

        platform = jax.default_backend()
    with _MODELS_LOCK:
        m = _MODELS.get(platform)
        if m is None:
            m = _MODELS[platform] = CompileModel(platform)
        return m


def reset_models() -> None:
    """Drop the singleton cache (tests repoint TUPLEX_COMPILE_MODEL_DIR)."""
    with _MODELS_LOCK:
        _MODELS.clear()


# ---------------------------------------------------------------------------
# the split decision
# ---------------------------------------------------------------------------

@dataclass
class SplitDecision:
    n_ops: int
    k: int                  # number of segments
    per: int                # max ops per segment
    predicted_compile_s: float   # summed over segments (serial; the compile
                                 # pool overlaps them, so wall is lower)
    boundary_s: float       # added per-boundary tax, (k-1) * unit cost
    budget_s: float         # tuplex.tpu.compileBudgetS (0 = unbounded)
    degrade: bool           # even the finest split blows the budget:
                            # compile on host CPU with device transfer
    fitted: bool            # curve came from measured points, not defaults
    reason: str = ""
    # op-index cut points (exclusive prefix lengths) when hazard costs
    # placed the boundaries; None = equal-size chunking by `per`
    boundaries: Optional[list] = None

    def describe(self) -> str:
        shape = (f"{self.n_ops} ops -> {self.k} segment(s) of <="
                 f"{self.per}")
        pred = (f"predicted compile {self.predicted_compile_s:.1f}s"
                f" ({'measured curve' if self.fitted else 'default curve'})"
                f", boundary tax {self.boundary_s:.2f}s")
        bud = f"budget {self.budget_s:.0f}s" if self.budget_s > 0 \
            else "no budget"
        tail = " — DEGRADED to host-CPU compile" if self.degrade else ""
        why = f" [{self.reason}]" if self.reason and not self.degrade else ""
        return f"stage-split tuner: {shape}; {pred}; {bud}{tail}{why}"


def _chunk_sizes(n: int, k: int) -> list[int]:
    per = math.ceil(n / k)
    sizes, left = [], n
    while left > 0:
        sizes.append(min(per, left))
        left -= per
    return sizes


def _weighted_chunks(costs: list, k: int) -> list:
    """Cut `costs` (per-op hazard costs) into <=k contiguous chunks with
    balanced COST (not count): the cut after op j lands where the cost
    prefix crosses the next 1/k-th of the total. Returns a list of
    exclusive cut indices (len k-1); every chunk keeps >=1 op."""
    n = len(costs)
    k = min(k, n)
    if k <= 1:
        return []
    total = sum(costs) or float(n)
    cuts, acc = [], 0.0
    for j, c in enumerate(costs):
        acc += c
        done = len(cuts)
        if done >= k - 1:
            break
        ops_left = n - (j + 1)
        chunks_left = k - done - 1
        if acc >= total * (done + 1) / k or ops_left <= chunks_left:
            cuts.append(j + 1)
    return cuts


def _cost_chunks(costs: list, k: int) -> list:
    """[(size, cost_sum)] for the k cost-balanced chunks of `costs`."""
    cuts = _weighted_chunks(costs, k)
    out, lo = [], 0
    for hi in cuts + [len(costs)]:
        out.append((hi - lo, sum(costs[lo:hi])))
        lo = hi
    return out


def plan_split(n_ops: int, budget_s: float,
               model: Optional[CompileModel] = None,
               max_segments: int = 32,
               prefer_fusion: bool = False,
               op_costs: Optional[list] = None) -> SplitDecision:
    """Pick the segment count for an `n_ops` fused stage.

    Minimizes predicted_compile + boundary tax over k; a positive
    `budget_s` is a ceiling on the predicted compile total — among the k
    that fit the budget the cheapest overall wins. With
    ``prefer_fusion=True`` (the CPU policy) the SMALLEST k that fits the
    budget wins instead: stage boundaries cost real memcpys there and the
    compile is a one-time cost the AOT artifact store amortizes away, so
    fusion is kept unless the predicted compile itself is pathological
    (flights' 43-op stage: >20 min / >120 GB on XLA:CPU). When nothing
    fits, the decision carries ``degrade=True`` with the cheapest split's
    numbers (what the accelerator WOULD cost): the physical planner then
    keeps the stage fused and pins its compile to the host CPU instead of
    the accelerator (_split_oversize).

    `op_costs` (compiler/graphlint: per-op construct-weighted compile
    seconds) rides ALONGSIDE the op-count curve: each candidate segment
    is predicted at max(power_law(size), hazard cost of its ops), and the
    chunk boundaries balance hazard COST rather than op count — two
    scatter-compaction ops can out-cost twenty elementwise ops, which op
    count alone can't see. When the hazard term (not the op-count curve)
    changes the chosen split, the decision says so (reason="hazard...")
    and carries the cost-balanced cut points in `boundaries`."""
    model = model or model_for()
    n_ops = max(int(n_ops), 1)
    if op_costs is not None and len(op_costs) != n_ops:
        # spread a mismatched cost vector evenly (e.g. census from a
        # traced fn whose op list was re-segmented since)
        tot = sum(op_costs)
        op_costs = [tot / n_ops] * n_ops
    curve, fitted = model.curve()
    if curve is None:
        return SplitDecision(
            n_ops, 1, n_ops, 0.0, 0.0, budget_s, degrade=False,
            fitted=False,
            reason=f"no compile observed on {model.platform}: kept fused")
    # per-boundary unit tax: the host-side dispatch+transfer sample plus
    # the MEASURED device occupancy of one extra dispatch (devprof's warm
    # launch→ready median; 0.0 until a profiled run exists)
    bcost = model.boundary_cost() + model.device_dispatch_cost()

    def candidates(costs):
        cs = []
        for k in range(1, min(n_ops, max_segments) + 1):
            if costs is None:
                chunks = [(s, 0.0) for s in _chunk_sizes(n_ops, k)]
            else:
                chunks = _cost_chunks(costs, k)
            segs = [max(model.predict(s), c) for s, c in chunks]
            bnd = (len(chunks) - 1) * bcost
            cs.append((k, max(s for s, _ in chunks), sum(segs), bnd,
                       max(segs)))
        return cs

    def choose(cands, per_segment):
        # op-count mode: the budget caps the summed serial compile (the
        # historical contract). Hazard mode: construct cost is CONSERVED
        # by splitting (the scatters don't go away), so a total-sum cap
        # could never be met by any k — what splitting buys is smaller
        # compile UNITS, so the budget caps the worst single segment.
        def fits(c):
            return budget_s <= 0 or \
                (c[4] if per_segment else c[2]) <= budget_s
        in_budget = [c for c in cands if fits(c)]
        if in_budget:
            key = (lambda c: c[0]) if prefer_fusion \
                else (lambda c: c[2] + c[3])
            return min(in_budget, key=key), False
        return min(cands, key=lambda c: c[2]), True

    hazard = op_costs is not None
    (k, per, comp, bnd, _worst), over = choose(candidates(op_costs), hazard)
    reason = ""
    if over:
        reason = (f"finest split still predicts {comp:.0f}s compile "
                  f"> budget {budget_s:.0f}s")
    boundaries = None
    if hazard:
        (k0, _, _, _, _), over0 = choose(candidates(None), False)
        if k != k0 or over != over0:
            reason = (
                f"hazard: construct-weighted compile cost picked "
                f"{'degrade' if over else f'k={k}'} (op-count curve alone "
                f"picked {'degrade' if over0 else f'k={k0}'})")
        if k > 1:
            boundaries = _weighted_chunks(op_costs, k)
    return SplitDecision(n_ops, k, per, comp, bnd, budget_s,
                         degrade=over, fitted=fitted, reason=reason,
                         boundaries=boundaries)


def log_decision(dec: SplitDecision) -> None:
    from ..utils.logging import get_logger

    log = get_logger("plan")
    (log.warning if dec.degrade else log.info)("%s", dec.describe())
