"""End-to-end serving driver: FailLite-managed cluster on this host.

Spins up worker cells hosting real JAX engines for the selected
architectures, serves batched client traffic, injects a crash, and
reports the two-step failover — controller MTTR next to client-observed
downtime.  This is the serving twin of `launch/train.py`.

Usage:
  PYTHONPATH=src python -m repro.launch.serve \
      [--archs qwen2.5-3b,rwkv6-3b] [--policy faillite] [--observe 30]
"""

from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default="qwen2.5-3b,rwkv6-3b,"
                                       "recurrentgemma-2b")
    ap.add_argument("--policy", default="faillite",
                    choices=["faillite", "full-warm", "full-cold",
                             "full-warm-k"])
    ap.add_argument("--sites", type=int, default=3)
    ap.add_argument("--servers-per-site", type=int, default=2)
    ap.add_argument("--headroom", type=float, default=0.3)
    ap.add_argument("--observe", type=float, default=30.0)
    ap.add_argument("--client-hz", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.testbed import MiniTestbed
    enable_compile_cache()
    archs = [a.strip() for a in args.archs.split(",") if a.strip()]
    print(f"deploying {len(archs)} applications under policy="
          f"{args.policy} on {args.sites}x{args.servers_per_site} cells "
          f"(real JAX engines — ~1 min of compiles)...")
    tb = MiniTestbed(apps_per_arch=1, archs=archs, seed=args.seed,
                     headroom=args.headroom, policy=args.policy,
                     n_sites=args.sites,
                     servers_per_site=args.servers_per_site)
    tb.deploy()
    for app in tb.apps:
        route = tb.router.lookup(app.id)
        warm = tb.controller.warm.get(app.id)
        print(f"  {app.id:28s} primary={route[0]} "
              f"warm={'%s@%s' % (warm[0].name, warm[1]) if warm else '-'}"
              f"{' [critical]' if app.critical else ''}")

    res = tb.run_failure_experiment(observe_s=args.observe,
                                    client_hz=args.client_hz)
    print(f"\ncrashed {res['victim']}; detected in "
          f"{res['detect_latency_s']*1e3:.0f} ms")
    s = res["summary"]
    print(f"recovery {s['recovery_rate']:.0%}  MTTR {s['mttr_avg']*1e3:.0f} ms  "
          f"accuracy cost {s['accuracy_reduction']:.2%}")
    for app_id, rec in res["records"].items():
        print(f"  {app_id:28s} {rec.mode:17s} "
              f"{'%.0f ms' % (rec.mttr*1e3) if rec.recovered else 'LOST':>9s}"
              f" -> {rec.variant}")
    print("client view:")
    for app_id, st in res["client_stats"].items():
        down = f"{st.downtime*1e3:.0f} ms" if st.downtime else "none"
        print(f"  {app_id:28s} ok={st.ok:4d} failed={st.failed:4d} "
              f"downtime={down}")
    tb.shutdown()


if __name__ == "__main__":
    main()
