"""loadgen_fleet — scenario-diverse multi-tenant load + the closed loop.

The scheduling analog of tools/chaos_fleet.py (CHAOS_r01): a REAL
multi-process stub fleet — worker subprocesses behind the production
supervisor + router — carries tenant-tagged load through the PR 16
admission/autoscaling control loop, and the subsystem's claims are
asserted, not assumed:

  diurnal_ramp         offered load ramps low -> high -> low; every
                       request is accounted and nothing is lost at
                       either edge of the ramp
  tenant_skew          tenant 'bulk' floods while 'rt' and 'std' pace;
                       the per-tenant token buckets (policy file
                       shipped to every worker via
                       FLAGS_sched_policy_file) cap the flood with the
                       typed QuotaExceededError, the weighted goodput
                       shares converge (Jain fairness index over
                       goodput/weight is the committed metric), and
                       realtime SLO attainment survives the flood
  flash_crowd          a cold simultaneous burst: absorbed as
                       completions + typed sheds, zero lost
  slow_client_trickle  low-rate traffic stays fast and unstarved while
                       the fleet is otherwise idle
  brownout_scaleout    HEADLINE: every live replica's device browns
                       out 60x (/readyz stays GREEN — the bad-rollout
                       shape rerouting cannot mitigate); the realtime
                       latency SLO starts burning, the fast-burn page
                       fires through the PR 11 alert sink, and
                       FleetAutoscaler scales the fleet OUT
                       (supervisor.scale_to) — reaction time from
                       injection to the scale-out decision is gated,
                       and the fresh healthy replica actually restores
                       the SLO. After /chaos restore + sustained quiet
                       it scales back IN (hysteresis: cooldown + quiet
                       window, never below min_replicas)
  priority_pressure    in-process GenerationServer under KV page
                       pressure: a realtime arrival preempts (parks)
                       the lowest-priority stream, its pages return to
                       the free list, the parked stream resumes and
                       completes, and kv.leak_check() stays clean

Usage:
  python tools/loadgen_fleet.py                       # full run, stdout
  python tools/loadgen_fleet.py --out SCHED_r01.json  # committed record
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the in-process priority_pressure scenario builds a tiny model; the
# fleet scenarios only talk HTTP to stub subprocesses. Neither needs
# an accelerator.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

SLO_THRESHOLD_MS = 150.0
REALTIME_SLO_FLOOR = 0.95
FAIRNESS_FLOOR = 0.80
SCALE_REACTION_BOUND_S = 15.0


def _feed(v=1.0):
    return [np.full((1, 4), v, np.float32)]


def _post(url, obj, timeout=10.0):
    import urllib.request
    opener = urllib.request.build_opener(
        urllib.request.ProxyHandler({}))
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with opener.open(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _wait(predicate, timeout=60.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def jain_index(shares):
    """Jain's fairness index over per-tenant normalized shares:
    1.0 = perfectly proportional, 1/n = one tenant has everything."""
    xs = [float(x) for x in shares if x is not None]
    if not xs or all(x == 0.0 for x in xs):
        return 0.0
    return (sum(xs) ** 2) / (len(xs) * sum(x * x for x in xs))


class TenantLoad:
    """Closed-loop tenant-tagged load: ``threads`` workers each submit
    one tagged request, wait for it, account the outcome (with
    latency), sleep ``pace_s``, repeat. ``pace_s`` 0 = flood. Every
    completed realtime latency can be direct-fed into an SLOMonitor so
    the burn-rate machinery sees exactly what the client saw."""

    def __init__(self, router, tenant, threads=1, pace_s=0.0,
                 monitor=None, slo_name=None):
        from paddle_tpu.serving.fleet import ReplicaError, resilience
        from paddle_tpu.serving.request import (
            DeadlineExceededError, QueueFullError, QuotaExceededError,
            ServerClosedError)
        self.router = router
        self.tenant = tenant
        self.pace_s = float(pace_s)
        self.monitor = monitor
        self.slo_name = slo_name
        self._quota_t = QuotaExceededError
        self._queue_t = QueueFullError
        self._deadline_t = DeadlineExceededError
        self._riding_t = (ReplicaError, resilience.ReplicaWedgedError,
                          ServerClosedError)
        self.counts = {"completed": 0, "shed_quota": 0,
                       "shed_queue": 0, "deadline": 0,
                       "riding_failed": 0, "lost": 0}
        self.latencies_ms: list = []
        self.in_flight = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._run,
                                          daemon=True)
                         for _ in range(threads)]

    def _account(self, exc, lat_ms):
        with self._lock:
            if exc is None:
                self.counts["completed"] += 1
                self.latencies_ms.append(lat_ms)
            elif isinstance(exc, self._quota_t):
                self.counts["shed_quota"] += 1
            elif isinstance(exc, self._queue_t):
                self.counts["shed_queue"] += 1
            elif isinstance(exc, self._deadline_t):
                self.counts["deadline"] += 1
            elif isinstance(exc, self._riding_t):
                self.counts["riding_failed"] += 1
            else:
                self.counts["lost"] += 1
        if exc is None and self.monitor is not None:
            self.monitor.observe(self.slo_name, lat_ms)

    def _run(self):
        while not self._stop.is_set():
            t0 = time.perf_counter()
            with self._lock:
                self.in_flight += 1
            try:
                fut = self.router.submit_many(
                    [_feed()], tenant=self.tenant)[0]
                fut.result(timeout=60)
                exc = None
            except Exception as e:  # noqa: BLE001 - accounted
                exc = e
            finally:
                with self._lock:
                    self.in_flight -= 1
            self._account(exc, (time.perf_counter() - t0) * 1e3)
            if self.pace_s:
                time.sleep(self.pace_s)

    def start(self):
        for t in self._threads:
            t.start()
        return self

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=60)

    def goodput_rps(self, elapsed_s):
        return self.counts["completed"] / max(1e-9, elapsed_s)

    def attainment(self, threshold_ms=SLO_THRESHOLD_MS):
        lats = self.latencies_ms
        if not lats:
            return 0.0
        return sum(1 for x in lats if x <= threshold_ms) / len(lats)

    def summary(self, elapsed_s):
        lats = sorted(self.latencies_ms)
        return {
            "counts": dict(self.counts),
            "goodput_rps": round(self.goodput_rps(elapsed_s), 1),
            "p50_ms": round(lats[len(lats) // 2], 1) if lats else None,
            "p99_ms": round(lats[int(len(lats) * 0.99)], 1)
            if lats else None,
            "slo_attainment": round(self.attainment(), 4),
        }


# ------------------------------------------------------------ fleet A
_POLICY = {
    "default": {"rate": 0.0, "burst": 64.0, "weight": 1.0,
                "priority": "standard"},
    "tenants": {
        "rt": {"rate": 0.0, "burst": 64.0, "weight": 4.0,
               "priority": "realtime"},
        "std": {"rate": 0.0, "burst": 64.0, "weight": 2.0,
                "priority": "standard"},
        # the flood tenant: capped so its weighted share matches the
        # paced tenants' (rt 20/s / w4 = std 10/s / w2 = bulk 5/s /
        # w1). Bucket rates are PER REPLICA (each worker's admission
        # controller is process-local, the standard distributed
        # rate-limiting posture), so the per-replica rate is the
        # fleet-wide budget divided by the 2 replicas.
        "bulk": {"rate": 2.5, "burst": 4.0, "weight": 1.0,
                 "priority": "batch"},
    },
}


def run_traffic_scenarios(verbose=True):
    """diurnal_ramp + tenant_skew + flash_crowd + slow_client_trickle
    over one 2-replica stub fleet with the tenant policy file shipped
    to every worker (the real FLAGS_sched_policy_file path)."""
    from paddle_tpu.serving import fleet

    log = (lambda m: print(f"  {m}", file=sys.stderr)) if verbose \
        else (lambda m: None)
    pol_path = os.path.join(tempfile.mkdtemp(prefix="paddle-sched-"),
                            "policy.json")
    with open(pol_path, "w") as f:
        json.dump(_POLICY, f)
    fac = fleet.ProcessReplicaFactory(
        extra_args=["--stub", "--stub-device-ms", "3",
                    "--stub-capacity", "128"],
        env={"JAX_PLATFORMS": "cpu",
             "FLAGS_sched_policy_file": pol_path})
    sup = fleet.ReplicaSupervisor(fac, 2, restart_backoff_ms=50)
    sup.start()
    router = fleet.FleetRouter(
        supervisor=sup, name="loadgen", health_interval_ms=100,
        retries=3, retry_backoff_ms_=5.0, retry_backoff_max_ms=80.0)
    out = {}
    try:
        assert router.wait_ready(2, timeout=120), \
            f"fleet never came up: {router.replica_states()}"

        # ---- scenario: diurnal ramp ------------------------------
        log("scenario: diurnal_ramp (low -> high -> low)")
        phases = []
        for name, threads, pace_s, dur_s in (
                ("low_am", 2, 0.1, 1.5), ("peak", 8, 0.01, 2.0),
                ("low_pm", 2, 0.1, 1.5)):
            load = TenantLoad(router, "default", threads=threads,
                              pace_s=pace_s).start()
            time.sleep(dur_s)
            load.stop()
            phases.append(dict(load.summary(dur_s), phase=name))
        out["diurnal_ramp"] = {
            "phases": phases,
            "peak_over_trough": round(
                phases[1]["goodput_rps"]
                / max(1e-9, phases[0]["goodput_rps"]), 2),
            "zero_lost": all(p["counts"]["lost"] == 0
                             for p in phases),
        }

        # ---- scenario: tenant skew (the fairness measurement) ----
        log("scenario: tenant_skew (bulk floods, rt/std pace)")
        dur_s = 6.0
        rt = TenantLoad(router, "rt", threads=4, pace_s=0.2).start()
        std = TenantLoad(router, "std", threads=2, pace_s=0.2).start()
        bulk = TenantLoad(router, "bulk", threads=4,
                          pace_s=0.0).start()
        time.sleep(dur_s)
        for x in (rt, std, bulk):
            x.stop()
        weights = {t: _POLICY["tenants"][t]["weight"]
                   for t in ("rt", "std", "bulk")}
        shares = {t: load.goodput_rps(dur_s) / weights[t]
                  for t, load in (("rt", rt), ("std", std),
                                  ("bulk", bulk))}
        fairness = {
            "jain_weighted": round(jain_index(shares.values()), 4),
            "weighted_shares_rps": {t: round(s, 2)
                                    for t, s in shares.items()},
            "weights": weights,
            "per_tenant": {t: load.summary(dur_s)
                           for t, load in (("rt", rt), ("std", std),
                                           ("bulk", bulk))},
        }
        out["tenant_skew"] = {
            "duration_s": dur_s,
            "fairness": fairness,
            "rt_slo_attainment": round(rt.attainment(), 4),
            "bulk_shed_typed": bulk.counts["shed_quota"],
            "zero_lost": all(x.counts["lost"] == 0
                             for x in (rt, std, bulk)),
        }
        log(f"  jain={fairness['jain_weighted']} "
            f"rt_attainment={out['tenant_skew']['rt_slo_attainment']} "
            f"bulk_shed={bulk.counts['shed_quota']}")

        # ---- scenario: flash crowd -------------------------------
        log("scenario: flash_crowd (cold simultaneous burst)")
        n_calls, per_call = 12, 16
        futs_box: list = []

        def _burst():
            futs_box.append(router.submit_many(
                [_feed() for _ in range(per_call)], tenant="default"))

        t0 = time.perf_counter()
        burst_threads = [threading.Thread(target=_burst)
                         for _ in range(n_calls)]
        for t in burst_threads:
            t.start()
        for t in burst_threads:
            t.join()
        done = shed = lost = 0
        for futs in futs_box:
            for f in futs:
                try:
                    f.result(timeout=60)
                    done += 1
                except Exception as e:  # noqa: BLE001 - accounted
                    from paddle_tpu.serving.request import \
                        QueueFullError
                    if isinstance(e, QueueFullError):
                        shed = shed + 1
                    else:
                        lost += 1
        drain_s = time.perf_counter() - t0
        out["flash_crowd"] = {
            "offered": n_calls * per_call, "completed": done,
            "shed_typed": shed, "lost": lost,
            "drain_s": round(drain_s, 2),
            "zero_lost": lost == 0,
        }

        # ---- scenario: slow-client trickle -----------------------
        log("scenario: slow_client_trickle")
        dur_s = 3.0
        trickle = TenantLoad(router, "rt", threads=1,
                             pace_s=0.5).start()
        time.sleep(dur_s)
        trickle.stop()
        s = trickle.summary(dur_s)
        out["slow_client_trickle"] = dict(
            s, zero_lost=s["counts"]["lost"] == 0,
            unstarved=s["counts"]["completed"] >= 4)
        return out
    finally:
        router.shutdown()
        sup.stop()


# ------------------------------------------------------------ fleet B
def run_brownout_scaleout(verbose=True):
    """The headline: slow-replica brownout -> fast-burn page ->
    FleetAutoscaler scale-out; restore + quiet -> scale-in.

    The brownout hits EVERY live replica (a bad rollout / thermal
    throttling shape): the router cannot route around it — a single
    slow replica is invisible fleet-wide precisely because
    least-outstanding routing starves it of traffic — so added
    capacity is the only mitigation, and the replica the autoscaler
    spawns comes up healthy and actually restores the SLO (the alert
    resolves through the same sink that fired it)."""
    from paddle_tpu.observability.registry import MetricRegistry
    from paddle_tpu.observability.slo import (BurnRule, LatencySLO,
                                              SLOMonitor)
    from paddle_tpu.serving import fleet
    from paddle_tpu.serving.scheduling import FleetAutoscaler

    log = (lambda m: print(f"  {m}", file=sys.stderr)) if verbose \
        else (lambda m: None)
    fac = fleet.ProcessReplicaFactory(
        extra_args=["--stub", "--stub-device-ms", "3",
                    "--stub-capacity", "128"],
        env={"JAX_PLATFORMS": "cpu"})
    sup = fleet.ReplicaSupervisor(fac, 2, restart_backoff_ms=50)
    sup.start()
    router = fleet.FleetRouter(
        supervisor=sup, name="scaleout", health_interval_ms=100,
        retries=2,
        # breaker neutralized ON PURPOSE: with every replica slow
        # there is no healthy peer to shed to — this scenario proves
        # the AUTOSCALER is the mitigation for a whole-fleet brownout
        breaker_failure_ratio=1.1, breaker_latency_ms=0.0)
    # seconds-scale burn windows so the run finishes in CI time; the
    # production default is the SRE-Workbook 5m/1h + 6h/3d pairs
    monitor = SLOMonitor(registry=MetricRegistry())
    monitor.add(LatencySLO(
        "loadgen_rt", metric="loadgen_rt_direct",
        threshold_ms=SLO_THRESHOLD_MS, target_fraction=0.95,
        burn_rules=(BurnRule("fast_burn", 1.5, 6.0, 2.0, "page"),
                    BurnRule("slow_burn", 3.0, 12.0, 1.0, "ticket"))))
    load = None
    asc = FleetAutoscaler(
        sup, monitor=monitor,
        queue_depth_fn=lambda: load.in_flight if load else 0,
        min_replicas=2, max_replicas=4, cooldown_s=2.0,
        scale_in_quiet_s=4.0, queue_high=50.0, interval_s=0.2,
        name="loadgen")
    try:
        assert router.wait_ready(2, timeout=120), \
            f"fleet never came up: {router.replica_states()}"
        load = TenantLoad(router, "rt", threads=8, pace_s=0.05,
                          monitor=monitor,
                          slo_name="loadgen_rt").start()
        # healthy baseline so the long burn window has good traffic
        for _ in range(10):
            monitor.evaluate()
            asc.evaluate()
            time.sleep(0.1)

        browned = sorted(sup.endpoints().items())
        log(f"brownout: {len(browned)} replicas, device 3ms -> 180ms")
        for _, url in browned:
            _post(url + "/chaos", {"device_ms": 180.0})
        t_inject = time.monotonic()
        reaction_s = None
        fired = False
        deadline = t_inject + 30.0
        while time.monotonic() < deadline:
            monitor.evaluate()
            decision = asc.evaluate()
            fired = fired or any(
                r == "fast_burn"
                for f in asc.snapshot()["firing"]
                for r in (f["rule"],))
            if decision is not None and decision["direction"] == "out":
                reaction_s = time.monotonic() - t_inject
                break
            time.sleep(0.1)
        assert reaction_s is not None, \
            f"no scale-out within 30s: {asc.snapshot()}"
        log(f"scale-out after {reaction_s:.1f}s "
            f"(fast_burn fired: {fired})")
        ready3 = router.wait_ready(3, timeout=60)
        ready_s = time.monotonic() - t_inject

        log("restore + quiet: waiting for scale-in")
        for _, url in browned:
            try:
                _post(url + "/chaos", {"restore": True,
                                       "device_ms": 3.0})
            except OSError:
                pass    # replica may have been retired meanwhile
        load.stop()
        scale_in = None
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            monitor.evaluate()
            decision = asc.evaluate()
            if decision is not None and decision["direction"] == "in":
                scale_in = decision
                break
            time.sleep(0.1)
        snap = asc.snapshot()
        return {
            "replicas_before": 2, "max_replicas": 4,
            "fast_burn_fired": bool(fired),
            "reaction_s": round(reaction_s, 2),
            "reaction_bound_s": SCALE_REACTION_BOUND_S,
            "scaled_fleet_ready": bool(ready3),
            "ready_s": round(ready_s, 2),
            "scaled_out": True,
            "scaled_in": scale_in is not None,
            "decisions": snap["decisions"],
            "load": load.summary(1.0)["counts"],
        }
    finally:
        asc.stop()
        router.shutdown()
        sup.stop()


# --------------------------------------------------------- in-process
def run_priority_pressure(verbose=True):
    """KV page pressure: a batch-class stream holds most of the page
    pool; a realtime arrival that cannot fit preempts (parks) it; the
    pages come back, the parked stream resumes to completion, and the
    refcount leak tripwire stays clean."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving.generation import GenerationServer
    from paddle_tpu.serving.scheduling import (AdmissionController,
                                               SchedulerPolicy,
                                               TenantPolicy)

    log = (lambda m: print(f"  {m}", file=sys.stderr)) if verbose \
        else (lambda m: None)
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny(use_flash_attention=False))
    m.eval()
    pol = SchedulerPolicy(tenants={
        "rt": TenantPolicy("rt", weight=4.0, priority="realtime"),
        "bulk": TenantPolicy("bulk", weight=1.0, priority="batch")})
    sched = AdmissionController(policy=pol, name="pressure")
    log("priority_pressure: bulk fills the page pool, rt preempts")
    with GenerationServer(m, max_batch=2, page_size=4, num_pages=8,
                          scheduler=sched, name="pressure") as srv:
        bulk_fut = srv.submit_generate([5, 6, 7, 8, 9, 10],
                                       max_new_tokens=20,
                                       tenant="bulk")
        # let bulk prefill and start decoding so it owns its pages
        for _ in bulk_fut:
            break
        rt_fut = srv.submit_generate([1, 2, 3, 4], max_new_tokens=8,
                                     tenant="rt")
        rt_tokens = rt_fut.result(timeout=120)
        bulk_tokens = bulk_fut.result(timeout=120)
        snap = srv.metrics_snapshot()
        counters = snap["counters"]
        leak = snap["kv_leak_check"]
        rec = {
            "rt_completed": len(rt_tokens) == 8,
            "bulk_completed": len(bulk_tokens) == 20,
            "parked": int(counters.get("parked", 0)),
            "resumed": int(counters.get("resumed", 0)),
            "preempted_failed": int(counters.get("preempted", 0)),
            "leak_check": leak,
            "page_leak_clean": bool(leak.get("ok", False)),
        }
    log(f"  parked={rec['parked']} resumed={rec['resumed']} "
        f"leak_ok={rec['page_leak_clean']}")
    return rec


# ------------------------------------------------------------- record
def run(out=None, verbose=True):
    t_start = time.time()
    traffic = run_traffic_scenarios(verbose=verbose)
    autoscale = run_brownout_scaleout(verbose=verbose)
    pressure = run_priority_pressure(verbose=verbose)

    skew = traffic["tenant_skew"]
    fairness = skew["fairness"]
    zero_lost = bool(
        traffic["diurnal_ramp"]["zero_lost"]
        and skew["zero_lost"]
        and traffic["flash_crowd"]["zero_lost"]
        and traffic["slow_client_trickle"]["zero_lost"]
        and autoscale["load"].get("lost", 0) == 0)
    invariants = {
        "zero_lost": zero_lost,
        "quota_sheds_typed": skew["bulk_shed_typed"] > 0,
        "fairness_floor": FAIRNESS_FLOOR,
        "fairness_above_floor":
            fairness["jain_weighted"] >= FAIRNESS_FLOOR,
        "realtime_slo_floor": REALTIME_SLO_FLOOR,
        "scale_out_observed": autoscale["scaled_out"],
        "fast_burn_drove_scaleout": autoscale["fast_burn_fired"],
        "scale_in_observed": autoscale["scaled_in"],
        "reaction_within_bound":
            autoscale["reaction_s"] <= SCALE_REACTION_BOUND_S,
        "preemption_observed": pressure["parked"] > 0,
        "parked_stream_resumed": pressure["resumed"] > 0,
        "page_leak_clean": pressure["page_leak_clean"],
    }
    for name, ok in invariants.items():
        if isinstance(ok, bool):
            assert ok, f"invariant {name} failed: " + json.dumps(
                {"traffic": traffic, "autoscale": autoscale,
                 "pressure": pressure}, default=str)[:2000]
    record = {
        "bench": "loadgen_fleet",
        "metric": "sched_control_loop",
        "schema": 1,
        "skipped": False,
        # the headline number: realtime SLO attainment while the
        # batch tenant floods (the "noisy neighbor" claim)
        "value": skew["rt_slo_attainment"],
        "unit": "fraction",
        "vs_baseline": round(
            skew["rt_slo_attainment"] / REALTIME_SLO_FLOOR, 4),
        "scenarios": ["diurnal_ramp", "tenant_skew", "flash_crowd",
                      "slow_client_trickle", "brownout_scaleout",
                      "priority_pressure"],
        "fairness": fairness,
        "autoscale": autoscale,
        "priority_pressure": pressure,
        "traffic": traffic,
        "invariants": invariants,
        "elapsed_s": round(time.time() - t_start, 1),
    }
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
            f.write("\n")
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="write the JSON record here")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()
    record = run(out=args.out, verbose=not args.quiet)
    json.dump(record, sys.stdout, indent=1, sort_keys=True)
    print()


if __name__ == "__main__":
    main()
