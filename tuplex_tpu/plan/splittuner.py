"""Stage-split cost function: compile-cost curve vs boundary tax.

Compile time grows superlinearly with the size of a fused stage, but
splitting trades compile seconds against a per-boundary cost — every extra
stage boundary pays a dispatch + D2H/H2D round trip (SystemML's fusion-plan
work, PAPERS: arXiv:1801.00829, and FusionStitching, arXiv:1811.05213, cost
the same tradeoff). ``plan_split`` decides from its arguments and the
constants below alone: the same stage on the same platform under the same
budget always gets the same split, whatever ran before.

Only XLA:CPU has a curve. A platform without one keeps its stages fused: a
curve nobody measured to win on the device would shape its plans.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

# power-law curve t(n) = a + b * n^c for XLA:CPU, anchored on measured
# compiles: zillow's 13-op stage compiles in ~40 s locally but flights' 43-op
# stage ran >20 min at >120 GB RSS before being killed (c >= ln(30)/ln(3.3)
# ~= 2.9 between those two anchors — the barrier-laden mega-fusions blow up
# XLA:CPU superlinearly).
CURVES = {"cpu": (0.3, 0.05, 2.5)}
# per-boundary dispatch + transfer seconds
BOUNDARY_S = {"cpu": 0.005}
_MAX_SEGMENTS = 32


def predict(platform: str, n_ops: int) -> Optional[float]:
    """Predicted compile seconds for a fused stage of `n_ops` operators on
    `platform`; None where the platform has no curve."""
    curve = CURVES.get(platform)
    if curve is None:
        return None
    a, b, c = curve
    return a + b * max(int(n_ops), 1) ** c


@dataclass
class SplitDecision:
    n_ops: int
    k: int                  # number of segments
    per: int                # max ops per segment
    predicted_compile_s: Optional[float]   # summed over segments (serial;
                            # the compile pool overlaps them, so wall is
                            # lower); None on a platform without a curve
    boundary_s: float       # added per-boundary tax, (k-1) * unit cost
    budget_s: float         # tuplex.tpu.compileBudgetS (0 = unbounded)
    over_budget: bool       # every split blows the budget: the cheapest
                            # one is taken anyway
    reason: str = ""
    # op-index cut points (exclusive prefix lengths) when hazard costs
    # placed the boundaries; None = equal-size chunking by `per`
    boundaries: Optional[list] = None

    def describe(self) -> str:
        shape = (f"{self.n_ops} ops -> {self.k} segment(s) of <="
                 f"{self.per}")
        pred = "no compile curve" if self.predicted_compile_s is None else (
            f"predicted compile {self.predicted_compile_s:.1f}s"
            f", boundary tax {self.boundary_s:.2f}s")
        bud = f"budget {self.budget_s:.0f}s" if self.budget_s > 0 \
            else "no budget"
        why = f" [{self.reason}]" if self.reason else ""
        return f"stage-split: {shape}; {pred}; {bud}{why}"


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


def plan_split(n_ops: int, budget_s: float, platform: str,
               prefer_fusion: bool = False,
               op_costs: Optional[list] = None) -> SplitDecision:
    """Pick the segment count for an `n_ops` fused stage on `platform`.

    Minimizes predicted_compile + boundary tax over k; a positive
    `budget_s` is a ceiling on the predicted compile total — among the k
    that fit the budget the cheapest overall wins. With
    ``prefer_fusion=True`` (the CPU policy) the SMALLEST k that fits the
    budget wins instead: stage boundaries cost real memcpys there and the
    compile is a one-time cost the AOT artifact store amortizes away, so
    fusion is kept unless the predicted compile itself is pathological
    (flights' 43-op stage: >20 min / >120 GB on XLA:CPU). When nothing
    fits, the decision carries ``over_budget=True`` with the split of the
    cheapest predicted compile.

    `op_costs` (compiler/graphlint: per-op construct-weighted compile
    seconds) rides ALONGSIDE the op-count curve: each candidate segment
    is predicted at max(power_law(size), hazard cost of its ops), and the
    chunk boundaries balance hazard COST rather than op count — two
    scatter-compaction ops can out-cost twenty elementwise ops, which op
    count alone can't see. When the hazard term (not the op-count curve)
    changes the chosen split, the decision says so (reason="hazard...")
    and carries the cost-balanced cut points in `boundaries`."""
    n_ops = max(int(n_ops), 1)
    if platform not in CURVES:
        return SplitDecision(
            n_ops, 1, n_ops, None, 0.0, budget_s, over_budget=False,
            reason=f"no compile curve for {platform}: kept fused")
    if op_costs is not None and len(op_costs) != n_ops:
        # spread a mismatched cost vector evenly (e.g. census from a
        # traced fn whose op list was re-segmented since)
        tot = sum(op_costs)
        op_costs = [tot / n_ops] * n_ops
    bcost = BOUNDARY_S.get(platform, 0.0)

    def candidates(costs):
        cs = []
        for k in range(1, min(n_ops, _MAX_SEGMENTS) + 1):
            if costs is None:
                chunks = [(s, 0.0) for s in _chunk_sizes(n_ops, k)]
            else:
                chunks = _cost_chunks(costs, k)
            segs = [max(predict(platform, s), c) for s, c in chunks]
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
        reason = (f"cheapest split still predicts {comp:.0f}s compile "
                  f"> budget {budget_s:.0f}s")
    boundaries = None
    if hazard:
        (k0, _, _, _, _), over0 = choose(candidates(None), False)
        if k != k0 or over != over0:
            reason = (
                f"hazard: construct-weighted compile cost picked "
                f"{'over budget' if over else f'k={k}'} (op-count curve "
                f"alone picked {'over budget' if over0 else f'k={k0}'})")
        if k > 1:
            boundaries = _weighted_chunks(op_costs, k)
    return SplitDecision(n_ops, k, per, comp, bnd, budget_s,
                         over_budget=over, reason=reason,
                         boundaries=boundaries)


def log_decision(dec: SplitDecision) -> None:
    from ..utils.logging import get_logger

    log = get_logger("plan")
    (log.warning if dec.over_budget else log.info)("%s", dec.describe())
