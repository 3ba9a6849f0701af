"""Chaos-controller scenario: score the fleet control loop through a
worker death + load spike (docs/control.md "Proving the loop").

The scenario is the closed loop end to end, all real components:

    HubServer <- Supervisor/Watcher <- N x scripts/control_worker.py
        ^                                   (SloTracker + lease drain)
        |
    Planner (attainment-fed decide() + GraceGate) -> SupervisorConnector

Timeline (all durations configurable):

1. **warm**: base-rate open-loop load against the worker pool; fleet
   attainment settles at ~1.0;
2. **event**: the offered rate spikes past pool capacity AND the victim
   worker dies deterministically (``DYN_FAULTS=worker.die.fail@N`` — it
   hard-exits on its N-th request; the watcher's restart backoff keeps
   it dead for the scenario). Queueing delay blows through the TTFT
   target, the workers' rolling SLO windows burn, the fold's `min`
   drops below the planner target, and the planner scales the pool up
   (the KV threshold is parked unreachable, so scale-up is attributable
   to the ATTAINMENT path alone);
3. **recover**: base load continues; scored: time from the death until
   fleet min attainment returns to the pre-event level, and the
   SLO-attained goodput fraction retained through the episode;
4. **cooldown**: load drops near zero; attainment headroom + idle load
   lets the planner scale back down — scored: the drain was graceful
   (lease revoked BEFORE the process stopped, no SIGTERM escalation).

Run directly it prints one JSON dict and exits non-zero if the loop
failed to close (no scale-up, infinite recovery, or an ungraceful
drain).

``--connector operator`` (or ``run_scenario(connector="operator")``)
drives the SAME scenario through the planner's OTHER scale connector:
the worker pool is deployed as a ``deploy/graphs/*`` spec
(scripts/control_graph.py) reconciled by the ``GraphOperator``, and
the planner scales by editing the spec in hub KV
(``OperatorConnector`` — the reference's planner-patches-CRD mode).
The recovery and revoke-before-stop drain contracts are asserted on
the reconciled watcher exactly as on the supervisor path.
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dynamo_tpu.engine.kv_ledger import quiesce_census  # noqa: E402
from dynamo_tpu.llm.planner import (  # noqa: E402
    Planner,
    PlannerConfig,
    SupervisorConnector,
)
from dynamo_tpu.runtime.distributed import DistributedRuntime  # noqa: E402
from dynamo_tpu.runtime.hub.server import HubServer  # noqa: E402
from dynamo_tpu.sdk.supervisor import Supervisor, Watcher  # noqa: E402
from dynamo_tpu.utils import counters  # noqa: E402

NS = "chaos"
COMPONENT = "backend"
WATCHER = "decoder"
WORKER_SCRIPT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "control_worker.py"
)


def _defaults() -> dict:
    """Tiny-scale defaults (CI smoke finishes in ~35 s on 2 cores)."""
    return dict(
        workers0=2,            # initial pool
        max_budget=4,          # planner chip budget (1 chip per replica)
        lanes=4,               # parallel lanes per worker
        service_s=0.08,        # per-request service time
        ttft_s=0.2,            # SLO target the tracker judges against
        base_rps=30.0,
        spike_rps=120.0,
        low_rps=4.0,
        warm_s=3.0,
        spike_s=5.0,
        recover_max_s=14.0,
        cooldown_max_s=16.0,
        die_at_hit=60,         # victim request count at death
        adjust_s=0.5,          # planner adjustment interval
    )


async def _load_phase(
    client, rate: float, duration: float, results: list, tasks: set
) -> None:
    """Open-loop arrivals at `rate` for `duration` seconds."""
    loop = asyncio.get_running_loop()
    end = loop.time() + duration

    async def one():
        t0 = loop.time()
        ok = True
        try:
            stream = await client.round_robin({"req": 1})
            async for _ in stream:
                pass
        except Exception:  # noqa: BLE001 — a failed request is honest
            # degradation data, not a harness error
            ok = False
        results.append((loop.time(), loop.time() - t0, ok))

    period = 1.0 / rate
    while loop.time() < end:
        t = asyncio.ensure_future(one())
        tasks.add(t)
        t.add_done_callback(tasks.discard)
        await asyncio.sleep(period)


def _attain_min(planner) -> float:
    att = planner.aggregator.attainment() if planner.aggregator else {}
    return min((v["min"] for v in att.values()), default=1.0)


async def run_scenario(connector: str = "supervisor", **overrides) -> dict:
    p = {**_defaults(), **overrides}
    hub = HubServer()
    await hub.start("127.0.0.1", 0)
    hub_addr = f"127.0.0.1:{hub.port}"

    worker_env = {
        "CHAOS_NS": NS,
        "CHAOS_COMPONENT": COMPONENT,
        "CHAOS_SERVICE_S": str(p["service_s"]),
        "CHAOS_LANES": str(p["lanes"]),
        "CHAOS_TTFT_S": str(p["ttft_s"]),
        "CHAOS_VICTIM": "0",
        # deterministic death: wid 0 exits on its N-th request
        "DYN_FAULTS": f"worker.die.fail@{p['die_at_hit']}",
        # control-plane chaos needs no accelerator: keep jax on the CPU
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
    }
    op = None
    hub_client = None
    if connector == "supervisor":
        sup = Supervisor(hub_addr=hub_addr)
        sup.watchers[WATCHER] = Watcher(
            name=WATCHER,
            args=[sys.executable, WORKER_SCRIPT],
            env=dict(worker_env),
            numprocesses=p["workers0"],
            # the dead victim must STAY dead for the scenario: recovery
            # is the planner's job here, not the restart loop's
            restart_backoff_s=120.0,
        )
        watcher = sup.watchers[WATCHER]
        await sup.start()
    elif connector == "operator":
        # the planner-patches-spec mode: deploy the SAME chaos pool as
        # a graph spec; the GraphOperator reconciles replica edits
        import json as _json

        from dynamo_tpu.runtime.hub.client import HubClient
        from dynamo_tpu.sdk.operator import GRAPH_PREFIX, GraphOperator

        graph_entry = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "control_graph.py"
        ) + ":ChaosDecoder"
        # DYN_LEASE_TTL: dead victims must vanish from discovery on the
        # recovery clock's timescale (control_worker.py pins its own)
        op = GraphOperator(
            hub_addr, extra_env={**worker_env, "DYN_LEASE_TTL": "1.5"}
        )
        await op.start()
        hub_client = await HubClient.connect(hub_addr)
        spec = {
            "entry": graph_entry,
            "services": {COMPONENT: {
                "workers": p["workers0"],
                "restart_backoff_s": 120.0,
            }},
        }
        await hub_client.kv_put(
            GRAPH_PREFIX + "chaos", _json.dumps(spec).encode()
        )
        for _ in range(200):
            if "chaos" in op.deployments:
                break
            await asyncio.sleep(0.05)
        if "chaos" not in op.deployments:
            raise RuntimeError("operator never reconciled the chaos spec")
        _, sup = op.deployments["chaos"]
        watcher = sup.watchers[COMPONENT]
    else:
        raise ValueError(f"unknown connector {connector!r}")

    observer = await DistributedRuntime.from_settings(hub_addr=hub_addr)
    client = await (
        observer.namespace(NS).component(COMPONENT).endpoint("generate").client()
    )
    await client.wait_for_instances()

    cfg = PlannerConfig(
        namespace=NS,
        decode_component=COMPONENT,
        disagg=False,
        metric_pull_interval_s=0.1,
        adjustment_interval_s=p["adjust_s"],
        min_endpoint=1,
        max_chip_budget=p["max_budget"],
        decode_engine_num_chips=1,
        # park the KV threshold unreachable: scale-up through THIS
        # scenario must come from the attainment path
        decode_kv_scale_up_threshold=1e9,
        decode_kv_scale_down_threshold=0.2,
        slo_attainment_target=0.99,
        scale_up_grace_rounds=0,
        scale_down_grace_rounds=2,
        # rounds are 0.5 s here: give a freshly spawned python worker
        # comfortably more than its ~1-2 s boot before its desired slot
        # reads as phantom (decay would re-add and overshoot the budget)
        desired_decay_rounds=8,
    )
    if connector == "supervisor":
        conn = SupervisorConnector(sup, {COMPONENT: WATCHER})
    else:
        from dynamo_tpu.sdk.operator import OperatorConnector

        conn = OperatorConnector(
            hub_client, "chaos", {COMPONENT: COMPONENT},
            max_replicas=p["max_budget"],
        )
    planner = Planner(observer, conn, cfg)
    ups0 = counters.get("planner_scale_up_total")
    downs0 = counters.get("planner_scale_down_total")
    await planner.start()

    loop = asyncio.get_running_loop()
    t0 = loop.time()
    results: list[tuple[float, float, bool]] = []
    tasks: set = set()
    timeline: list[dict] = []
    stop_sampling = asyncio.Event()

    async def sampler():
        while not stop_sampling.is_set():
            timeline.append(
                {
                    "t": round(loop.time() - t0, 2),
                    "attain_min": round(_attain_min(planner), 4),
                    "alive": watcher.alive_count(),
                    "procs": watcher.numprocesses,
                    "decision": (
                        planner.last_decision.reason
                        if planner.last_decision else ""
                    ),
                }
            )
            await asyncio.sleep(0.25)

    sampler_task = asyncio.create_task(sampler())

    # -- phase 1: warm ---------------------------------------------------
    await _load_phase(client, p["base_rps"], p["warm_s"], results, tasks)

    # -- phase 2: spike (the victim dies mid-spike via DYN_FAULTS) -------
    alive_before = watcher.alive_count()
    spike_start = loop.time() - t0
    await _load_phase(client, p["spike_rps"], p["spike_s"], results, tasks)

    # death time: first sample where the live count dropped
    t_death = next(
        (s["t"] for s in timeline
         if s["t"] >= spike_start and s["alive"] < alive_before),
        spike_start,
    )

    # -- phase 3: recover at base load, until attainment heals -----------
    pre = [
        s["attain_min"] for s in timeline
        if spike_start - 2.0 <= s["t"] < spike_start
    ]
    pre_attain = round(statistics.fmean(pre), 4) if pre else 1.0
    recover_level = min(pre_attain, cfg.slo_attainment_target)
    t_recovered = None
    deadline = loop.time() + p["recover_max_s"]
    while loop.time() < deadline:
        await _load_phase(client, p["base_rps"], 0.5, results, tasks)
        now_t = loop.time() - t0
        if now_t > t_death and _attain_min(planner) >= recover_level:
            t_recovered = now_t
            break

    # -- phase 4: cooldown: near-idle load -> scale-down + drain ---------
    peak_procs = max(s["procs"] for s in timeline)
    drain_deadline = loop.time() + p["cooldown_max_s"]
    while loop.time() < drain_deadline:
        await _load_phase(client, p["low_rps"], 0.5, results, tasks)
        if watcher.numprocesses < peak_procs and any(
            e[0] == "drained" for e in watcher.events
        ):
            break

    if tasks:
        await asyncio.gather(*list(tasks), return_exceptions=True)
    stop_sampling.set()
    await sampler_task
    await planner.stop()
    drain_events = list(watcher.events)
    await observer.shutdown()
    if op is not None:
        await op.stop()  # tears down the reconciled supervisor
    else:
        await sup.stop()
    if hub_client is not None:
        await hub_client.close()
    await hub.stop()

    # ---------------------------------------------------------------- score
    def frac_attained(lo: float, hi: float) -> float:
        win = [
            (ok and lat <= p["ttft_s"])
            for (t, lat, ok) in results
            if lo <= t - t0 < hi
        ]
        return round(sum(win) / len(win), 4) if win else 1.0

    pre_frac = frac_attained(0.0, spike_start)
    event_end = (t_recovered if t_recovered is not None
                 else spike_start + p["spike_s"] + p["recover_max_s"])
    event_frac = frac_attained(t_death, event_end)
    drained_wids = [w for (e, w) in drain_events if e == "drained"]
    drain_clean = bool(drained_wids) and all(
        # revoke must precede the drained exit, with no SIGTERM escalation
        ("lease_revoked", w) in drain_events
        and drain_events.index(("lease_revoked", w))
        < drain_events.index(("drained", w))
        and ("sigterm", w) not in drain_events
        for w in drained_wids
    )
    post = [s["attain_min"] for s in timeline[-4:]]
    return {
        "scenario": {
            "connector": connector,
            "workers_initial": p["workers0"],
            "chip_budget": p["max_budget"],
            "base_rps": p["base_rps"],
            "spike_rps": p["spike_rps"],
            "faults": f"worker.die.fail@{p['die_at_hit']}",
            "ttft_target_s": p["ttft_s"],
        },
        "event": {
            "t_spike_s": round(spike_start, 2),
            "t_death_s": round(t_death, 2),
        },
        "attainment": {
            "pre": pre_attain,
            "floor_during": round(
                min(
                    (s["attain_min"] for s in timeline if s["t"] >= t_death),
                    default=1.0,
                ), 4,
            ),
            "post": round(statistics.fmean(post), 4) if post else None,
            "target": cfg.slo_attainment_target,
        },
        "time_to_recover_s": (
            round(t_recovered - t_death, 2) if t_recovered is not None else None
        ),
        "goodput": {
            "pre_frac": pre_frac,
            "event_frac": event_frac,
            "retained": (
                round(event_frac / pre_frac, 4) if pre_frac else None
            ),
        },
        "scaling": {
            "ups": int(counters.get("planner_scale_up_total") - ups0),
            "downs": int(counters.get("planner_scale_down_total") - downs0),
            # chips are held by RUNNING processes: the dead victim's
            # watcher slot stays in `procs` (it would restart after the
            # scenario) but its chip is free — the budget metric is the
            # peak LIVE count
            "peak_alive": max(s["alive"] for s in timeline),
            "peak_slots": peak_procs,
            "final_workers": watcher.numprocesses,
        },
        "drain": {"clean": drain_clean, "events": drain_events},
        # workers are subprocess Sim engines — no in-process paged KV —
        # so the quiesce census is the honest degenerate one (zero
        # engines, zero orphans); any in-process ledger would be scored
        "kv_census": await asyncio.to_thread(quiesce_census, []),
        "requests": len(results),
        "timeline": timeline,
    }


def run(**overrides) -> dict:
    return asyncio.run(run_scenario(**overrides))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--connector", default="supervisor",
        choices=["supervisor", "operator"],
        help="planner scale connector: direct Supervisor calls, or "
             "spec edits reconciled by the GraphOperator",
    )
    args = ap.parse_args(argv)
    out = run(connector=args.connector)
    print(json.dumps(out, indent=2))
    ok = (
        out["scaling"]["ups"] >= 1
        and out["time_to_recover_s"] is not None
        and out["drain"]["clean"]
        and out["kv_census"]["ok"]
    )
    if not ok:
        print(
            f"control loop FAILED to close ({args.connector} connector)",
            file=sys.stderr,
        )
        return 1
    print(
        f"control loop closed ({args.connector} connector): recovered in "
        f"{out['time_to_recover_s']}s, goodput retained "
        f"{out['goodput']['retained']}, drain clean", file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
