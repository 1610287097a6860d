"""Run registry + cross-run regression ledger (ISSUE 16).

Every training run and serving run appends ONE schema-versioned
headline record to the ``TPUFLOW_REGISTRY_PATH`` JSONL — goodput
fraction, tokens/s, TTFT/ITL percentiles (from the mergeable buckets
when the snapshot carries them), ``hbm_peak_frac``, git commit + dirty
flag, and platform provenance. The sensors existed (PRs 13–15); this
file is the memory that lets anything *compare* them. The one-shot
importer backfills ``BENCH_r*.json`` captures of the benchmark this
repository had before ``benchmark/`` (none are committed; ROADMAP.md D4).

Durability contract:

- **Atomic append.** One ``os.write`` of one full line on an
  ``O_APPEND`` fd — concurrent writers interleave whole lines, never
  characters (POSIX pipe-buf-sized appends), and a crash mid-append
  leaves at most one torn final line.
- **Torn-line-tolerant reads.** ``read_registry`` skips any line that
  does not parse (or lacks the record shape) instead of raising — a
  registry survives the crash that tore it.
- **Tolerant metric extraction.** Legacy records predate the PR 15 keys
  (``hbm_peak_frac``, ``programs_ledger``, ``fleet_snapshot_path``);
  every extractor here degrades to "metric absent", never ``KeyError``
  — the r01–r04 backfill exercises exactly that.

Regression math is the PR 15 detector idiom reused host-side: the last
value vs the trailing window's **median + MAD** (``TPUFLOW_REGISTRY_
WINDOW`` / ``TPUFLOW_REGISTRY_ZMADS``), so one jittery round does not
read as a regression and a real cliff does. ``python -m tpuflow.obs
trend`` / ``compare`` render it jax-free.
"""

from __future__ import annotations

import json
import os
import subprocess
import time
from typing import Any, Iterable

from tpuflow.obs import recorder as _rec
from tpuflow.utils import knobs

SCHEMA = 1

# Registry filename the backfill CLI defaults to (beside the BENCH_r*.json
# records) when TPUFLOW_REGISTRY_PATH is unset.
DEFAULT_BASENAME = "TPU_REGISTRY.jsonl"

# (metric name, path into the bench compact-summary digest). Every
# lookup is guarded — a legacy digest missing a path yields an absent
# metric, never a KeyError (the r01–r04 backfill hits this on the
# post-PR-15 keys: hbm_peak_frac, programs_ledger, fleet snapshots).
_DIGEST_PATHS: tuple[tuple[str, tuple[str, ...]], ...] = (
    ("host_combined_gbps", ("host_combined_gbps",)),
    ("disk_combined_gbps", ("disk_combined_gbps",)),
    ("train_mfu", ("train", "mfu")),
    ("train_tokens_per_s", ("train", "tokens_per_s")),
    ("best_mfu_sweep", ("best_mfu_sweep",)),
    # exit-3 gate inputs
    ("spec_decode_numerics_ok", ("spec_decode", "numerics_ok")),
    ("spec_decode_speedup", ("spec_decode", "speedup")),
    ("serve_tokens_per_s", ("serving", "tokens_per_s")),
    ("serve_vs_sequential", ("serving", "vs_sequential")),
    ("serve_ttft_p99_s", ("serving", "ttft_p99_s")),
    ("serve_itl_p99_s", ("serving", "itl_p99_s")),
    ("hbm_peak_frac", ("serving", "hbm_peak_frac")),
    # exit-6 gate input
    ("paged_vs_slot", ("serving_paged", "vs_slot")),
    ("paged_tokens_per_s", ("serving_paged", "tokens_per_s")),
    # ISSUE 18: front-door router headline (absent on pre-router
    # records — trend/compare degrade to "metric absent" by the same
    # guarded walk as every other path here). router_dropped must be 0.
    ("router_requests", ("serving_router", "router_requests")),
    ("router_reroutes", ("serving_router", "router_reroutes")),
    ("router_dropped", ("serving_router", "router_dropped")),
    # exit-4 gate inputs
    ("int8_weight_only_speedup", ("int8_weight_only", "speedup")),
    ("int8_fused_native_speedup", ("int8_fused_native", "speedup")),
    # exit-5 gate inputs
    ("flash_crossover_T", ("flash_crossover_T",)),
    ("flash_fused_vs_split_T2048", ("flash_fused_vs_split_T2048",)),
    ("flash_fwdbwd_auto_T512", ("flash_fwdbwd_auto_T512",)),
    ("exposed_comm_s", ("exposed_comm_s",)),
)

# Metrics where DOWN is the good direction; everything else is
# higher-is-better (throughputs, speedups, fractions-of-peak).
_LOWER_IS_BETTER_TOKENS = (
    "ttft", "itl", "exposed_comm", "hbm_peak", "hbm_used", "slo_",
    "compile_s", "dropped", "reroute",
)


def lower_is_better(metric: str) -> bool:
    return any(tok in metric for tok in _LOWER_IS_BETTER_TOKENS)


# -------------------------------------------------------------- records
def registry_path(default: str | None = None) -> str | None:
    """The armed registry file, or ``default`` when the knob is unset
    (None disables the implicit run-end appends)."""
    return knobs.raw("TPUFLOW_REGISTRY_PATH") or default


def git_stamp(repo: str | None = None) -> tuple[str | None, bool | None]:
    """(commit, dirty) for ``repo`` (default: this package's checkout);
    (None, None) when git is unavailable — provenance is best-effort."""
    if repo is None:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        )))
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=repo,
            capture_output=True, text=True, timeout=5,
        ).stdout.strip() or None
        if commit is None:
            return None, None
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], cwd=repo,
            capture_output=True, text=True, timeout=5,
        ).stdout.strip())
        return commit, dirty
    except (OSError, subprocess.SubprocessError):
        return None, None


def make_record(
    kind: str,
    metrics: dict[str, float],
    *,
    source: str,
    run_id: str | None = None,
    platform: str | None = None,
    git: str | None = None,
    git_dirty: bool | None = None,
    ts: float | None = None,
) -> dict[str, Any]:
    if ts is None:
        ts = time.time()
    if run_id is None:
        run_id = f"{kind}-{int(ts)}-{os.getpid()}"
    rec: dict[str, Any] = {
        "schema": SCHEMA,
        "run_id": run_id,
        "ts": round(float(ts), 3),
        "kind": kind,
        "source": source,
        "metrics": dict(metrics),
    }
    if platform is not None:
        rec["platform"] = platform
    if git is not None:
        rec["git"] = git
    if git_dirty is not None:
        rec["git_dirty"] = git_dirty
    return rec


def append_record(path: str, record: dict) -> bool:
    """Crash-safe single-line append: the whole line lands in ONE
    O_APPEND write, so concurrent appenders interleave records, not
    bytes, and a crash tears at most the final line (which
    ``read_registry`` skips). Failures return False, never raise."""
    try:
        data = (json.dumps(record, sort_keys=True, default=str) + "\n").encode()
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, data)
        finally:
            os.close(fd)
    except OSError:
        return False
    _rec.event(
        "registry.append",
        path=path,
        run_id=record.get("run_id"),
        run_kind=record.get("kind"),
        metrics=len(record.get("metrics") or ()),
    )
    return True


def read_registry(path: str) -> list[dict]:
    """Every well-formed record in file order. A torn final line (crash
    mid-append), a corrupt line, or a non-record JSON value is skipped —
    reading a damaged registry never raises."""
    out: list[dict] = []
    try:
        f = open(path, encoding="utf-8", errors="replace")
    except OSError:
        return out
    with f:
        for line in f:
            if not line.endswith("\n"):
                continue  # torn tail: the append died mid-write
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and isinstance(
                rec.get("metrics"), dict
            ):
                out.append(rec)
    return out


# ------------------------------------------------- metric extraction
def _num(v: Any) -> float | None:
    if isinstance(v, bool):
        return 1.0 if v else 0.0
    if isinstance(v, (int, float)) and v == v:  # NaN-free
        return float(v)
    return None


def _walk(d: Any, path: tuple[str, ...]) -> Any:
    for key in path:
        if not isinstance(d, dict):
            return None
        d = d.get(key)
    return d


def digest_metrics(digest: dict | None) -> dict[str, float]:
    """Flat metrics from a bench compact-summary digest. Tolerant by
    construction: every path is walked with .get, a missing or
    non-numeric leaf is simply absent from the result."""
    out: dict[str, float] = {}
    if not isinstance(digest, dict):
        return out
    for name, path in _DIGEST_PATHS:
        v = _num(_walk(digest, path))
        if v is not None:
            out[name] = v
    return out


def bench_metrics(parsed: dict | None) -> tuple[dict[str, float], dict]:
    """(metrics, provenance) from any generation of bench record:
    r01's bare metric/value, r02–r03's full-record ``extra`` shape,
    r05's compact ``summary`` digest. Absent keys degrade to absent
    metrics — never KeyError (the backfill's legacy records miss every
    post-PR-15 key)."""
    out: dict[str, float] = {}
    prov: dict[str, Any] = {}
    if not isinstance(parsed, dict):
        return out, prov
    v = _num(parsed.get("value"))
    if v is not None:
        out["host_combined_gbps"] = v
    v = _num(parsed.get("vs_baseline"))
    if v is not None:
        out["vs_baseline"] = v
    summary = parsed.get("summary")
    if isinstance(summary, dict):
        out.update(digest_metrics(summary))
        plat = _walk(summary, ("train", "platform"))
        if isinstance(plat, str):
            prov["platform"] = plat
        if isinstance(summary.get("git"), str):
            prov["git"] = summary["git"]
        return out, prov
    extra = parsed.get("extra")
    if isinstance(extra, dict):
        v = _num(_walk(extra, ("tiers", "disk", "combined_gbps")))
        if v is not None:
            out["disk_combined_gbps"] = v
        train = extra.get("train")
        if isinstance(train, dict):
            for name, key in (
                ("train_mfu", "mfu"),
                ("train_tokens_per_s", "tokens_per_s"),
            ):
                v = _num(train.get(key))
                if v is not None:
                    out[name] = v
            if isinstance(train.get("platform"), str):
                prov["platform"] = train["platform"]
    return out, prov


def snapshot_metrics(snap: dict) -> dict[str, float]:
    """Headline metrics from a live goodput/serve ``/status`` snapshot.
    TTFT/ITL percentiles come from the mergeable histogram buckets when
    the snapshot carries them (the fleet-exact source), falling back to
    the pre-aggregated gauges."""
    out: dict[str, float] = {}
    for key in (
        "goodput_fraction", "tokens_per_s", "mfu", "step_rate",
        "hbm_peak_frac", "hbm_used_frac", "serve_tokens_per_s",
        "serve_requests", "serve_slo_violations", "serve_queue_depth",
        "nonfinite_steps",
        # ISSUE 18: router headline keys (present only when the
        # snapshot IS a front-door /status — legacy and replica
        # snapshots simply lack them).
        "router_requests", "router_reroutes", "router_dropped",
    ):
        v = _num(snap.get(key))
        if v is not None:
            out[key] = v
    from tpuflow.obs import fleet as _fleet

    for which in ("ttft", "itl"):
        h = snap.get(f"serve_{which}_hist")
        p = _fleet.hist_percentiles(h) if isinstance(h, dict) else None
        for q in ("p50", "p95", "p99"):
            v = _num(p.get(q)) if p else _num(
                snap.get(f"serve_{which}_{q}_s")
            )
            if v is not None:
                out[f"serve_{which}_{q}_s"] = v
    return out


def maybe_append_live(kind: str, snap: dict | None = None) -> bool:
    """Run-end hook (gang train legs, serve_forever): append this
    process's headline to the registry IF ``TPUFLOW_REGISTRY_PATH`` is
    armed — a single knob read when it is not. Never raises."""
    path = registry_path()
    if not path:
        return False
    try:
        if snap is None:
            from tpuflow.obs import goodput as _goodput

            snap = _goodput.live().snapshot()
        metrics = snapshot_metrics(snap)
        commit, dirty = git_stamp()
        platform = "tpu" if "hbm_limit_bytes" in snap else None
        rec = make_record(
            kind, metrics, source=f"{kind}:live", platform=platform,
            git=commit, git_dirty=dirty,
        )
        return append_record(path, rec)
    except Exception:
        return False


# ------------------------------------------------------------- backfill
def record_from_bench_file(path: str) -> dict | None:
    """One registry record from a BENCH_r*.json driver capture; None
    when the file is unreadable. A record whose ``parsed`` is null
    (r04's truncated tail) still imports — with whatever the tail's
    last complete JSON line yields, possibly no metrics at all."""
    try:
        with open(path, encoding="utf-8", errors="replace") as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(raw, dict):
        return None
    parsed = raw.get("parsed")
    if not isinstance(parsed, dict):
        # r04: the driver's 2000-char tail truncated the record and
        # parsed landed null. Salvage the last complete JSON line.
        tail = raw.get("tail")
        parsed = None
        if isinstance(tail, str):
            for line in reversed(tail.splitlines()):
                line = line.strip()
                if line.startswith("{") and line.endswith("}"):
                    try:
                        cand = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(cand, dict):
                        parsed = cand
                        break
    metrics, prov = bench_metrics(parsed)
    base = os.path.basename(path)
    stem = base.rsplit(".", 1)[0]
    n = raw.get("n")
    return make_record(
        "bench",
        metrics,
        source=f"backfill:{base}",
        run_id=stem,
        platform=prov.get("platform"),
        git=prov.get("git"),
        ts=float(n) if isinstance(n, (int, float)) else 0.0,
    )


def backfill_bench(bench_dir: str, path: str) -> int:
    """One-shot importer: append a record per BENCH_r*.json under
    ``bench_dir`` that the registry does not already hold (idempotent —
    rerunning imports nothing). Returns the number appended."""
    try:
        names = sorted(
            n for n in os.listdir(bench_dir)
            if n.startswith("BENCH_r") and n.endswith(".json")
        )
    except OSError:
        return 0
    seen = {r.get("run_id") for r in read_registry(path)}
    appended = 0
    for name in names:
        rec = record_from_bench_file(os.path.join(bench_dir, name))
        if rec is None or rec["run_id"] in seen:
            continue
        if append_record(path, rec):
            appended += 1
            seen.add(rec["run_id"])
    return appended


# ---------------------------------------------------------- trend math
def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def _mad(vals: list[float], med: float) -> float:
    return _median([abs(v - med) for v in vals])


def metric_series(
    records: Iterable[dict],
) -> dict[str, list[tuple[str, float]]]:
    """metric -> [(run_id, value)] in record order."""
    series: dict[str, list[tuple[str, float]]] = {}
    for rec in records:
        rid = str(rec.get("run_id", "?"))
        for m, v in (rec.get("metrics") or {}).items():
            fv = _num(v)
            if fv is not None:
                series.setdefault(m, []).append((rid, fv))
    return series


def verdict_rows(
    history: list[dict],
    current: dict[str, float],
    *,
    window: int | None = None,
    zmads: float | None = None,
) -> list[dict]:
    """Per-metric verdicts for ``current`` against the trailing window
    of ``history`` records — the PR 15 median+MAD spike detector reused
    host-side. A metric with no history is "new"; a last-vs-median
    deviation inside ``zmads`` robust deviations (with a 1% jitter
    floor, so an all-identical window does not make any change
    infinitely significant) is "ok"; outside it, the metric's
    good-direction decides "improved" vs "REGRESSED"."""
    if window is None:
        window = knobs.get_int("TPUFLOW_REGISTRY_WINDOW")
    if zmads is None:
        zmads = knobs.get_float("TPUFLOW_REGISTRY_ZMADS")
    series = metric_series(history)
    rows: list[dict] = []
    for metric in sorted(set(series) | set(current)):
        cur = _num(current.get(metric))
        past = [v for _, v in series.get(metric, [])][-window:]
        row: dict[str, Any] = {
            "metric": metric,
            "n": len(past),
            "last": cur,
        }
        if cur is None:
            row["verdict"] = "absent"
        elif not past:
            row["verdict"] = "new"
        else:
            med = _median(past)
            mad = _mad(past, med)
            delta = cur - med
            # 1.4826*MAD ~ sigma for normal jitter; the max() floor
            # keeps a constant history (MAD 0) from flagging noise.
            scale = max(1.4826 * mad, 0.01 * abs(med), 1e-12)
            z = delta / scale
            row.update(
                median=round(med, 6), mad=round(mad, 6),
                delta=round(delta, 6), z=round(z, 2),
            )
            if abs(z) <= zmads:
                row["verdict"] = "ok"
            else:
                good_down = lower_is_better(metric)
                improved = delta < 0 if good_down else delta > 0
                row["verdict"] = "improved" if improved else "REGRESSED"
        rows.append(row)
    return rows


def trend_rows(
    records: list[dict],
    *,
    metrics: list[str] | None = None,
    window: int | None = None,
    zmads: float | None = None,
) -> list[dict]:
    """The registry's newest record judged against its own trailing
    window (``obs trend``). ``metrics`` filters the rows."""
    if not records:
        return []
    rows = verdict_rows(
        records[:-1],
        dict(records[-1].get("metrics") or {}),
        window=window,
        zmads=zmads,
    )
    if metrics:
        keep = set(metrics)
        rows = [r for r in rows if r["metric"] in keep]
    return rows


def compare_rows(rec_a: dict, rec_b: dict) -> list[dict]:
    """Per-metric A→B deltas over the union of both records' metrics; a
    side missing the metric reads "absent" (legacy records, by design)."""
    ma = rec_a.get("metrics") or {}
    mb = rec_b.get("metrics") or {}
    rows: list[dict] = []
    for metric in sorted(set(ma) | set(mb)):
        a, b = _num(ma.get(metric)), _num(mb.get(metric))
        row: dict[str, Any] = {"metric": metric, "a": a, "b": b}
        if a is None or b is None:
            row["verdict"] = "absent"
        else:
            row["delta"] = round(b - a, 6)
            if a != 0:
                row["delta_pct"] = round(100.0 * (b - a) / abs(a), 2)
            if b == a:
                row["verdict"] = "same"
            else:
                good_down = lower_is_better(metric)
                improved = b < a if good_down else b > a
                row["verdict"] = "improved" if improved else "REGRESSED"
        rows.append(row)
    return rows


def _fmt(v: Any) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def format_rows(rows: list[dict], columns: tuple[str, ...]) -> str:
    """Aligned text table (the CLI rendering)."""
    headers = columns
    body = [[_fmt(r.get(c)) for c in headers] for r in rows]
    widths = [
        max(len(h), *(len(b[i]) for b in body)) if body else len(h)
        for i, h in enumerate(headers)
    ]
    lines = ["  ".join(h.ljust(w) for h, w in zip(headers, widths))]
    for b in body:
        lines.append("  ".join(c.ljust(w) for c, w in zip(b, widths)))
    return "\n".join(lines)
