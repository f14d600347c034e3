"""benchtrend: render the banked bench trajectory (stdlib only).

Every bench round banks one ``BENCH_r{n}.json`` artifact; benchguard
judges the newest against that history, but nothing *shows* the
trajectory. benchtrend does:

    python -m tools.benchtrend 'BENCH_r*.json' [--json]

renders a markdown table — one row per banked round with the headline
value, a per-metric direction arrow against the previous comparable
round (improvement/regression judged by the metric's direction, the
same ``resolve_direction`` inference benchguard uses), MFU when
present, and a flag on CPU-fallback rounds (older artifacts may carry
``extras.fallback_cpu``: a round measured on a forced-CPU fallback must
never be mistaken for a hardware ceiling). ``--json`` emits the same
rows as JSON for tooling.

Same import-light constraint as tools/benchguard (json/glob only, no
horovod_tpu, no jax) so the CLI works in any interpreter that can read
the artifacts.
"""

from __future__ import annotations

import glob as glob_mod
import json
from typing import List, Optional

from ..benchguard import _unwrap, resolve_direction

#: relative moves under this read as flat ("→"), not up/down
FLAT_EPSILON = 0.005


def load_rounds(pattern: str) -> List[dict]:
    """Every readable round matching the glob, sorted by round number
    (the wrapper's ``n`` field when present, else filename). Rounds that
    banked no parse (wedged runs: ``parsed: null``) are kept as
    placeholder rows — a hole in the trajectory is information."""
    out = []
    for path in sorted(glob_mod.glob(pattern)):
        try:
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = _unwrap(doc)
        n = doc.get("n") if isinstance(doc, dict) else None
        out.append({"n": n if isinstance(n, int) else None,
                    "path": path, "parsed": parsed})
    out.sort(key=lambda r: (r["n"] if r["n"] is not None else 10 ** 9,
                            r["path"]))
    return out


def load_history_dump(path: str) -> List[dict]:
    """A live job's health history as a trajectory: accepts either a
    ``GET /history`` dump (``{"ranks": {rank: {"series": ...}}}``) or a
    single rank's ``HOROVOD_HEALTH_FILE`` on-exit dump
    (``{"rank": k, "series": ...}``) and synthesizes one pseudo-round
    per sample point so history renders through the same table/arrow
    pipeline as banked ``BENCH_r*.json`` rounds. Multi-rank dumps prefix
    metrics ``rank{k}/`` — prefix, not suffix, so benchguard's
    ``resolve_direction`` suffix inference (``_ms`` → lower-is-better)
    still judges the underlying series name. Returns ``[]`` on an
    unreadable or shapeless file (the CLI maps that to exit 2)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return []
    if not isinstance(doc, dict):
        return []
    if isinstance(doc.get("ranks"), dict):
        per_rank = [(str(rank), snap)
                    for rank, snap in sorted(doc["ranks"].items())
                    if isinstance(snap, dict)]
    else:
        per_rank = [(str(doc.get("rank", 0)), doc)]
    multi = len(per_rank) > 1
    points = []  # (ts, metric, value)
    for rank, snap in per_rank:
        series = snap.get("series")
        if not isinstance(series, dict):
            continue
        for name, body in sorted(series.items()):
            samples = body.get("samples") if isinstance(body, dict) else None
            if not isinstance(samples, list):
                continue
            metric = f"rank{rank}/{name}" if multi else name
            for p in samples:
                if isinstance(p, (list, tuple)) and len(p) == 2 \
                        and isinstance(p[1], (int, float)):
                    points.append((float(p[0]), metric, float(p[1])))
    points.sort()
    return [{"n": i, "path": path,
             "parsed": {"metric": metric, "value": value, "unit": None}}
            for i, (_, metric, value) in enumerate(points)]


def _pct(cur: float, prev: float) -> Optional[float]:
    if prev == 0:
        return None
    return (cur - prev) / abs(prev)


def build_rows(rounds: List[dict]) -> List[dict]:
    """Flatten rounds into display rows with trend judgement: each row
    carries ``arrow`` (↑/↓/→ — the raw move), ``delta_pct`` vs the
    previous round of the SAME metric, and ``regression`` (True when
    the move goes the metric's wrong way)."""
    rows: List[dict] = []
    last_by_metric: dict = {}
    for rnd in rounds:
        parsed = rnd["parsed"]
        if not isinstance(parsed, dict) or \
                not isinstance(parsed.get("value"), (int, float)):
            rows.append({"n": rnd["n"], "path": rnd["path"], "metric": None,
                         "value": None, "unit": None, "mfu": None,
                         "arrow": "", "delta_pct": None, "regression": False,
                         "fallback_cpu": False, "note": "no parsed result"})
            continue
        metric = parsed.get("metric")
        value = float(parsed["value"])
        extras = parsed.get("extras") or {}
        fallback = bool(extras.get("fallback_cpu"))
        arrow, delta, regression = "", None, False
        prev = last_by_metric.get(metric)
        if prev is not None:
            delta = _pct(value, prev)
            if delta is None or abs(delta) < FLAT_EPSILON:
                arrow = "→"
            else:
                arrow = "↑" if delta > 0 else "↓"
                better = resolve_direction(metric or "")
                regression = (delta < 0) if better == "higher" \
                    else (delta > 0)
        last_by_metric[metric] = value
        rows.append({"n": rnd["n"], "path": rnd["path"], "metric": metric,
                     "value": value, "unit": parsed.get("unit"),
                     "mfu": parsed.get("mfu"), "arrow": arrow,
                     "delta_pct": round(delta * 100, 2)
                     if delta is not None else None,
                     "regression": regression,
                     "fallback_cpu": fallback, "note": ""})
    return rows


def render_markdown(rows: List[dict]) -> str:
    """The human view: a markdown table plus the CPU-fallback caveat
    line when any round carries the flag."""
    lines = ["| round | metric | value | trend | mfu | flags |",
             "|---|---|---|---|---|---|"]
    flagged = []
    for row in rows:
        n = row["n"] if row["n"] is not None else "?"
        if row["metric"] is None:
            lines.append(f"| {n} | — | — | — | — | {row['note']} |")
            continue
        trend = row["arrow"]
        if row["delta_pct"] is not None and trend in ("↑", "↓"):
            trend += f" {row['delta_pct']:+g}%"
        if row["regression"]:
            trend += " ⚠ regression"
        mfu = f"{row['mfu']:.4f}" if isinstance(row["mfu"], float) else "—"
        flags = []
        if row["fallback_cpu"]:
            flags.append("CPU-fallback")
            flagged.append(str(n))
        lines.append(f"| {n} | {row['metric']} | {row['value']:g} "
                     f"| {trend or '—'} | {mfu} | {', '.join(flags) or '—'} |")
    if flagged:
        lines.append("")
        lines.append(
            f"> rounds {', '.join(flagged)} ran on the forced-CPU fallback "
            "(no chip answered) — their numbers are NOT hardware ceilings "
            "and must not anchor chip comparisons.")
    return "\n".join(lines)
