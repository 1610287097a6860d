#!/usr/bin/env python
"""Babysitter for live runs and serving fleets.

Follow mode (``--follow [url]``): poll a LIVE run's metrics endpoint
(tpuflow.obs.export, opted in via TPUFLOW_OBS_HTTP_PORT on the run) and
print one status line per poll — step, step rate, tokens/s, rolling MFU,
goodput-so-far, last loss. The url defaults to 127.0.0.1:$TPUFLOW_OBS_HTTP_PORT;
TPU_WATCH_FOLLOW_INTERVAL_S (default 5) sets the cadence and
TPU_WATCH_MAX_S (default 11h) the deadline.

Fleet mode (``--fleet [target]``): the multi-replica twin (ISSUE 14) —
poll EVERY serving replica's /status through the fleet observatory
(``tpuflow.obs.fleet``) and print a fleet headline line (summed
QPS/queue/tokens-per-s, occupancy-weighted decode utilization,
fleet-exact TTFT/ITL p99 from merged histogram buckets, SLO count)
plus one line per replica with its health score. ``target`` is a
registration dir or comma URL list; omitted, the TPUFLOW_FLEET_*
knobs resolve it. A replica answering garbage (a /status read
mid-write) or nothing at all is marked STALE — the watcher never
crashes on a dying replica; that is the event it exists to report.

Both live modes run the declarative alert engine (ISSUE 16,
``tpuflow.obs.alerts``) over every poll and print ``ALERT ...
FIRED/RESOLVED`` lines on the lifecycle edges — SLO burn rate
(two-window AND-gate), HBM headroom, goodput drop, health collapse,
stale replicas — deduplicated in between, thresholds from the
``TPUFLOW_ALERT_*`` knobs.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Runnable from anywhere, like the other standalone tools: the knob
# registry lives in the package.
sys.path.insert(0, REPO)
from tpuflow.utils import knobs  # noqa: E402


def follow(url: str, interval: float, max_s: float) -> int:
    """Poll ``<url>/status`` (the live export endpoint's JSON view) and
    print one babysitter line per poll. Unreachable polls are reported
    and retried — the endpoint appears when the gang's member 0 starts
    training and vanishes across requeues, both routine mid-watch."""
    import urllib.request

    from tpuflow.obs import alerts as alerts_mod

    def fmt(st: dict, key: str, spec: str = "{:.3g}") -> str:
        v = st.get(key)
        return spec.format(v) if isinstance(v, (int, float)) else "-"

    # Alert engine (ISSUE 16): the same declarative rules the /alerts
    # endpoint serves, evaluated over each poll — a babysitter session
    # prints ALERT lines on the fired/resolved edges, deduplicated
    # in between.
    eng = alerts_mod.AlertEngine()
    deadline = time.time() + max_s
    while time.time() < deadline:
        stamp = time.strftime("%H:%M:%S")
        try:
            with urllib.request.urlopen(
                url.rstrip("/") + "/status", timeout=5
            ) as r:
                st = json.loads(r.read().decode())
        except (OSError, ValueError) as e:
            print(
                f"[tpu_watch {stamp}] follow: {url} unreachable ({e}); "
                f"retry in {interval:.0f}s",
                flush=True,
            )
        else:
            hbm = ""
            if "hbm_used_frac" in st or "hbm_used_bytes" in st:
                # Device observatory (ISSUE 15): HBM residency of the
                # busiest local device — keys only exported when the
                # backend reports memory_stats, so the segment simply
                # disappears off-TPU.
                hbm = (
                    f" hbm={fmt(st, 'hbm_used_frac', '{:.2f}')}"
                    f"/{fmt(st, 'hbm_peak_frac', '{:.2f}')}pk"
                    f" ({fmt(st, 'hbm_used_bytes', '{:.2e}')}B)"
                )
            serving = ""
            if "serve_slot_occupancy" in st:
                # A serving process (tpuflow.infer.serve feeds these):
                # the operator's live queue/TTFT/throughput view, plus
                # the engine-time ledger fractions and SLO count
                # (ISSUE 13) — one line answers "is this replica
                # earning its HBM".
                serving = (
                    f" | serve q={st.get('serve_queue_depth', '-')} "
                    f"occ={fmt(st, 'serve_slot_occupancy', '{:.2f}')} "
                    f"tok/s={fmt(st, 'serve_tokens_per_s', '{:.0f}')} "
                    f"ttft50={fmt(st, 'serve_ttft_p50_s', '{:.3f}')}s "
                    f"p99={fmt(st, 'serve_ttft_p99_s', '{:.3f}')}s "
                    f"itl99={fmt(st, 'serve_itl_p99_s', '{:.4f}')}s "
                    f"idle={fmt(st, 'serve_idle_fraction', '{:.2f}')} "
                    f"dec={fmt(st, 'serve_decode_fraction', '{:.2f}')} "
                    f"pre={fmt(st, 'serve_prefill_fraction', '{:.2f}')} "
                    f"slo={st.get('serve_slo_violations', '-')} "
                    f"done={st.get('serve_requests', '-')}"
                )
                if "serve_pages_host" in st or "serve_pages_disk" in st:
                    # Tiered prefix cache (ISSUE 19): lower-tier page
                    # counts, only when a tier is armed on the replica.
                    serving += (
                        f" host={st.get('serve_pages_host', '-')} "
                        f"disk={st.get('serve_pages_disk', '-')}"
                    )
            print(
                f"[tpu_watch {stamp}] step={st.get('step', '-')} "
                f"rate={fmt(st, 'step_rate')}/s "
                f"tok/s={fmt(st, 'tokens_per_s', '{:.0f}')} "
                f"mfu={fmt(st, 'mfu', '{:.4f}')} "
                f"goodput={fmt(st, 'goodput_fraction', '{:.3f}')} "
                f"loss={fmt(st, 'loss', '{:.4f}')} "
                f"up={fmt(st, 'uptime_s', '{:.0f}')}s" + hbm + serving,
                flush=True,
            )
            for t in eng.observe(status=st):
                print(
                    f"[tpu_watch {stamp}] "
                    + alerts_mod.format_transition(t),
                    flush=True,
                )
        time.sleep(interval)
    print("[tpu_watch] follow deadline reached", flush=True)
    return 0


def fleet(target: str | None, interval: float, max_s: float) -> int:
    """Poll the serving fleet and print one headline + one line per
    replica per interval (tpuflow.obs.fleet does discovery, per-replica
    timeout/backoff, staleness marking, and the histogram merge)."""
    from tpuflow.obs import alerts as alerts_mod
    from tpuflow.obs import fleet as fleet_mod

    obsy = fleet_mod.FleetObservatory(target)
    # Fleet-scope alerting (ISSUE 16): burn-rate over the fleet's summed
    # violation counters, HBM headroom of the tightest replica, health
    # collapse, stale replicas.
    eng = alerts_mod.AlertEngine()
    deadline = time.time() + max_s
    while time.time() < deadline:
        stamp = time.strftime("%H:%M:%S")
        snap = obsy.poll()
        if not snap["replicas"]:
            print(
                f"[tpu_watch {stamp}] fleet: no replicas discovered "
                "(pass a registration dir / URL list or set "
                "TPUFLOW_FLEET_REPLICAS); retry in "
                f"{interval:.0f}s",
                flush=True,
            )
        else:
            print(
                f"[tpu_watch {stamp}] "
                + fleet_mod.format_fleet_line(snap["fleet"]),
                flush=True,
            )
            for row in snap["replicas"]:
                print(fleet_mod.format_replica_line(row), flush=True)
            # End-to-end tracing (ISSUE 18): when the merged fleet TTFT
            # histogram carries exemplars, name the concrete trace
            # behind the p99 bucket — `python -m tpuflow.obs trace`
            # turns it into the per-hop breakdown.
            ex = fleet_mod.hist_exemplar(
                snap["fleet"].get("ttft_hist"), 0.99
            )
            if ex is not None:
                print(
                    f"[tpu_watch {stamp}] ttft p99 exemplar: trace "
                    f"{ex} (python -m tpuflow.obs trace <request_id> "
                    "resolves it)",
                    flush=True,
                )
            for t in eng.observe(fleet=snap["fleet"]):
                print(
                    f"[tpu_watch {stamp}] "
                    + alerts_mod.format_transition(t),
                    flush=True,
                )
        time.sleep(interval)
    print("[tpu_watch] fleet deadline reached", flush=True)
    return 0


if __name__ == "__main__":
    if "--fleet" in sys.argv:
        i = sys.argv.index("--fleet")
        fleet_target = None
        if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
            fleet_target = sys.argv[i + 1]
        sys.exit(
            fleet(
                fleet_target,
                float(os.environ.get("TPU_WATCH_FOLLOW_INTERVAL_S", "5")),
                float(os.environ.get("TPU_WATCH_MAX_S", str(11 * 3600))),
            )
        )
    if "--follow" in sys.argv:
        i = sys.argv.index("--follow")
        if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
            follow_url = sys.argv[i + 1]
        else:
            follow_url = (
                "http://127.0.0.1:"
                f"{knobs.raw('TPUFLOW_OBS_HTTP_PORT', '8080')}"
            )
        sys.exit(
            follow(
                follow_url,
                float(os.environ.get("TPU_WATCH_FOLLOW_INTERVAL_S", "5")),
                float(os.environ.get("TPU_WATCH_MAX_S", str(11 * 3600))),
            )
        )
    sys.exit("usage: tpu_watch.py --follow [url] | --fleet [dir|urls]")
