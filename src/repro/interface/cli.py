"""Command-line tools (paper Listing 1): dj-process / dj-analyze analogues,
all thin shells over the shared Pipeline API (repro.api).

  python -m repro.interface.cli process --config recipe.{json,yaml}
  python -m repro.interface.cli sql "SELECT ..." [--dataset_path x.jsonl]
  python -m repro.interface.cli explain --config recipe.{json,yaml}
  python -m repro.interface.cli explain --sql "SELECT ..." [--dataset_path ..]
  python -m repro.interface.cli analyze --dataset_path x.jsonl [--auto]
  python -m repro.interface.cli list-ops
  python -m repro.interface.cli runner --cluster_dir DIR [--capacity N]
  python -m repro.interface.cli submit --config recipe.{json,yaml} \
      --cluster_dir DIR [--tenant T] [--job_id ID] [--wait]
  python -m repro.interface.cli cluster-status --cluster_dir DIR \
      [--slo] [--tenants]
  python -m repro.interface.cli trace JOB_ID --cluster_dir DIR [--out F]
"""
from __future__ import annotations

import argparse
import sys


def _print_report(report) -> None:
    print(f"recipe={report.recipe} in={report.n_in} out={report.n_out} "
          f"seconds={report.seconds:.2f} plan={report.plan}")
    for row in report.per_op:
        print(f"  {row['op']:40s} {row['seconds']:.3f}s "
              f"{row['in']}->{row['out']} ({row['speed']:.0f} samples/s)")
    if report.insight:
        print(report.insight)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="dj")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_proc = sub.add_parser("process", help="run a recipe")
    p_proc.add_argument("--config", required=True)
    p_proc.add_argument("--np", type=int, default=0)

    p_sql = sub.add_parser("sql", help="compile and run a SQL query over the "
                                       "shared logical plan")
    p_sql.add_argument("query")
    p_sql.add_argument("--dataset_path", default=None,
                       help="input jsonl (or quote a path in FROM)")
    p_sql.add_argument("--export_path", default=None)
    p_sql.add_argument("--np", type=int, default=0)

    p_ex = sub.add_parser("explain", help="show the optimized plan/segments "
                                          "without processing the dataset "
                                          "(probes a small head sample to "
                                          "estimate op speeds)")
    p_ex.add_argument("--config", default=None)
    p_ex.add_argument("--sql", default=None, dest="sql_query",
                      help="explain a SQL query instead of a recipe")
    p_ex.add_argument("--dataset_path", default=None,
                      help="input jsonl for --sql (or quote a path in FROM)")

    p_an = sub.add_parser("analyze", help="compute default stats + report")
    p_an.add_argument("--dataset_path", required=True)
    p_an.add_argument("--auto", action="store_true",
                      help="auto-discover every applicable stat op")
    p_an.add_argument("--limit", type=int, default=0,
                      help="analyze only the first N samples")

    sub.add_parser("list-ops", help="print the OP registry")

    p_run = sub.add_parser("runner", help="run a cluster job runner: lease "
                                          "jobs from a shared cluster_dir, "
                                          "execute them with heartbeats and "
                                          "segment-checkpoint failover")
    p_run.add_argument("--cluster_dir", required=True)
    p_run.add_argument("--runner_id", default=None)
    p_run.add_argument("--capacity", type=int, default=1,
                       help="concurrent jobs this runner holds leases for")
    p_run.add_argument("--lease_ttl", type=float, default=None,
                       help="seconds a lease survives without a heartbeat")
    p_run.add_argument("--poll", type=float, default=0.2)
    p_run.add_argument("--defer", type=float, default=None, dest="defer_s",
                       help="placement deference window in seconds (how long "
                            "a worse-placed runner leaves a job for a better "
                            "one before claiming it anyway)")
    p_run.add_argument("--once", action="store_true",
                       help="claim and run at most one job, then exit")

    p_sub = sub.add_parser("submit", help="durably enqueue a recipe into a "
                                          "cluster queue (executed by "
                                          "whichever runners lease it) under "
                                          "a tenant identity")
    p_sub.add_argument("--config", required=True)
    p_sub.add_argument("--cluster_dir", required=True)
    p_sub.add_argument("--tenant", default=None,
                       help="owning tenant (quota admission, fair-share "
                            "claiming, per-tenant SLOs); defaults to the "
                            "recipe's tenant field or the default tenant")
    p_sub.add_argument("--job_id", default=None)
    p_sub.add_argument("--wait", action="store_true",
                       help="block until the job reaches a terminal state")

    p_cs = sub.add_parser("cluster-status", help="print the cluster queue "
                                                 "overview (runners, leases, "
                                                 "queue depth)")
    p_cs.add_argument("--cluster_dir", required=True)
    p_cs.add_argument("--slo", action="store_true",
                      help="also print SLO rollups from the event log "
                           "(queue-wait percentiles, per-runner AND "
                           "per-tenant throughput, failover/preemption "
                           "counts)")
    p_cs.add_argument("--tenants", action="store_true",
                      help="also print the per-tenant rollup (weight, quota, "
                           "live jobs, claims granted)")

    p_tr = sub.add_parser("trace", help="merge a job's span spills into one "
                                        "Chrome-trace JSON (open in "
                                        "chrome://tracing or Perfetto)")
    p_tr.add_argument("job_id")
    p_tr.add_argument("--cluster_dir", required=True)
    p_tr.add_argument("--out", default=None,
                      help="output path (default TRACE_<job_id>.json)")

    args = ap.parse_args(argv)

    if args.cmd == "list-ops":
        from repro.core.registry import list_ops, op_info

        for n in list_ops():
            info = op_info(n)
            print(f"{n:40s} {info['type']:12s} {info['doc'][:60]}")
        return 0

    if args.cmd == "process":
        from repro.api import Pipeline
        from repro.core.recipes import Recipe

        pipe = Pipeline.from_recipe(Recipe.load(args.config))
        if args.np:
            pipe = pipe.options(np=args.np)
        _, report = pipe.execute()
        _print_report(report)
        return 0

    if args.cmd == "sql":
        from repro.api.sql import SQLError, parse_sql, sql

        try:
            q = parse_sql(args.query)
            base = args.dataset_path or (q.source if q.source_is_path
                                         else None)
            out_path = args.export_path or (base + ".out.jsonl" if base
                                            else None)
            pipe = sql(args.query, dataset_path=args.dataset_path,
                       export_path=out_path)
        except SQLError as e:
            print(f"sql error [{e.kind}]: {e}", file=sys.stderr)
            return 1
        if args.np:
            pipe = pipe.options(np=args.np)
        _, report = pipe.execute()
        _print_report(report)
        if out_path:
            print(f"exported -> {out_path}")
        return 0

    if args.cmd == "explain":
        from repro.api import Pipeline
        from repro.core.recipes import Recipe

        if bool(args.sql_query) == bool(args.config):
            print("explain needs exactly one of --config or --sql",
                  file=sys.stderr)
            return 1
        if args.sql_query:
            from repro.api.sql import SQLError, sql

            try:
                pipe = sql(args.sql_query, dataset_path=args.dataset_path)
            except SQLError as e:
                print(f"sql error [{e.kind}]: {e}", file=sys.stderr)
                return 1
        else:
            pipe = Pipeline.from_recipe(Recipe.load(args.config))
        info = pipe.explain()
        print(f"recipe={info['recipe']} engine={info['engine']} np={info['np']} "
              f"streaming={info['streaming']}")
        print(f"requested: {' -> '.join(info['requested'])}")
        print(f"optimized: {' -> '.join(info['plan'])}")
        for nd in info.get("nodes", []):
            if nd["kind"] in ("source", "sink"):
                extra = " ".join(f"{k}={v}" for k, v in nd.items()
                                 if k not in ("kind", "name"))
                print(f"  {nd['kind']:8s} {nd['name']:40s} {extra}")
                continue
            flags = "".join(f" [{f}]" for f in
                            ("pushdown", "columnar", "barrier", "stateful")
                            if nd.get(f))
            print(f"  {nd['kind']:8s} {nd['name']:40s} "
                  f"reads={','.join(nd['reads']) or '-'} "
                  f"writes={','.join(nd['writes']) or '-'}{flags}")
        for rw in info.get("rewrites", []):
            if not rw["changed"]:
                print(f"  rule {rw['rule']:22s} [no-op]")
            elif rw["before"] != rw["after"]:
                print(f"  rule {rw['rule']:22s} [changed] "
                      f"{' -> '.join(rw['before'])}")
                print(f"       {'':22s}        => {' -> '.join(rw['after'])}")
            else:
                # annotation-only rule: the chain is unchanged, the diff is
                # in the marks it set
                detail = " ".join(f"{k}={v}" for k, v in
                                  sorted(rw.get("detail", {}).items()))
                print(f"  rule {rw['rule']:22s} [marked] {detail}")
        for i, seg in enumerate(info["segments"]):
            kind = "barrier" if seg["barrier"] else (
                "stateful" if seg.get("stateful") else "stream ")
            print(f"  segment {i} [{kind}]: {' -> '.join(seg['ops'])}")
        return 0

    if args.cmd == "runner":
        from repro.api.cluster import ClusterQueue, ClusterRunner, PlacementPolicy

        queue = ClusterQueue(args.cluster_dir)
        if args.lease_ttl:
            queue.lease_ttl = args.lease_ttl
        policy = None if args.defer_s is None \
            else PlacementPolicy(defer_seconds=args.defer_s)
        runner = ClusterRunner(queue, runner_id=args.runner_id,
                               capacity=args.capacity,
                               lease_ttl=args.lease_ttl, poll=args.poll,
                               policy=policy)
        print(f"runner {runner.runner_id} leasing from {queue.dir} "
              f"(capacity={runner.capacity}, ttl={runner.lease_ttl}s)",
              flush=True)
        if args.once:
            ran = runner.run_once()
            print(f"runner {runner.runner_id}: "
                  f"{'ran one job' if ran else 'queue empty'}")
            return 0
        try:
            runner.run_forever()
        except KeyboardInterrupt:
            runner.drain()
        return 0

    if args.cmd == "submit":
        import time as _time

        from repro.api.cluster import (AdmissionDenied, ClusterQueue,
                                       TERMINAL)
        from repro.core.recipes import Recipe

        queue = ClusterQueue(args.cluster_dir)
        recipe = Recipe.load(args.config)
        try:
            jid = queue.submit(recipe.to_dict(), job_id=args.job_id,
                               tenant=args.tenant)
        except AdmissionDenied as e:
            print(f"admission denied [{e.scope}]: {e}", file=sys.stderr)
            return 1
        spec = queue.read_spec(jid)
        print(f"submitted {jid} tenant={spec.get('tenant')} "
              f"-> {queue.dir}", flush=True)
        if not args.wait:
            return 0
        while True:
            state = queue.state_of(jid)
            if state in TERMINAL:
                break
            _time.sleep(0.2)
        st = queue.status(jid, verbose=False)
        print(f"job {jid} {st['state']}"
              + (f" error={st['error']}" if st.get("error") else ""))
        return 0 if st["state"] == "succeeded" else 1

    if args.cmd == "cluster-status":
        from repro.api.cluster import ClusterQueue

        cq = ClusterQueue(args.cluster_dir)
        ov = cq.overview()
        jobs = " ".join(f"{k}={v}" for k, v in sorted(ov["jobs"].items()))
        print(f"cluster {ov['cluster_dir']}")
        print(f"queue_depth={ov['queue_depth']} {jobs}")
        for c in ov["runners"]:
            live = "live" if c.get("alive") else "dead"
            print(f"  runner {c['runner_id']:28s} [{live}] "
                  f"active={c.get('active', 0)}/{c.get('capacity', 1)} "
                  f"throughput={c.get('throughput', 0.0):.1f}/s "
                  f"quarantines={c.get('quarantines', 0)} "
                  f"score={c.get('score', 0.0):.2f}")
        for l in ov["leases"]:
            mark = "EXPIRED" if l["expired"] else "live"
            print(f"  lease {l['job_id']} -> {l['runner_id']} "
                  f"attempt={l['attempt']} [{mark}]")
        for parent, rows in sorted(ov.get("sharded", {}).items()):
            print(f"  sharded {parent}: {len(rows)} tasks")
            for r in rows:
                extra = ""
                if r.get("resumed_at"):
                    extra += f" resumed_at={r['resumed_at']}"
                if r.get("n_out") is not None:
                    extra += f" n_out={r['n_out']}"
                if r.get("lease_expired"):
                    extra += " [EXPIRED]"
                print(f"    {r['kind']:8s} {r['task_id']:24s} "
                      f"{r['state']:10s} attempt={r.get('attempt', 0)} "
                      f"runner={r.get('runner_id') or '-'}{extra}")
        if args.slo:
            from repro.api.slo import cluster_slo

            slo = cluster_slo(args.cluster_dir)
            qw = slo["queue_wait"]
            print(f"slo queue_wait n={qw['n']} p50={qw['p50']:.3f}s "
                  f"p95={qw['p95']:.3f}s max={qw['max']:.3f}s")
            print(f"slo failovers={slo['failovers']} "
                  f"preempted={slo['preempted']} "
                  f"redispatches={slo['redispatches']} "
                  f"jobs_finished={slo['jobs_finished']} "
                  f"jobs_failed={slo['jobs_failed']}")
            for rid, t in slo["throughput"].items():
                print(f"  throughput {rid:28s} jobs={t['jobs']} "
                      f"rows={t['rows']} "
                      f"rows_per_second={t['rows_per_second']:.1f}")
            for name, t in slo.get("tenants", {}).items():
                tqw = t["queue_wait"]
                print(f"  tenant {name:24s} waits n={tqw['n']} "
                      f"p50={tqw['p50']:.3f}s p95={tqw['p95']:.3f}s "
                      f"finished={t['jobs_finished']} "
                      f"rows_per_second={t['rows_per_second']:.1f}")
        if args.tenants:
            for row in cq.tenant_overview():
                quota = row["max_live_jobs"]
                jobs = " ".join(f"{k}={v}"
                                for k, v in sorted(row["jobs"].items()))
                print(f"  tenant {row['tenant']:24s} "
                      f"weight={row['weight']:g} "
                      f"quota={'-' if quota is None else quota} "
                      f"live={row['live_jobs']} "
                      f"claims={row['claims_granted']:g} "
                      f"keys={row['api_keys']}"
                      + (f" [{jobs}]" if jobs else ""))
        return 0

    if args.cmd == "trace":
        import json

        from repro.api.cluster import ClusterQueue
        from repro.core import obs

        queue = ClusterQueue(args.cluster_dir)
        try:
            spec = queue.read_spec(args.job_id)
        except KeyError:
            print(f"no job {args.job_id!r} in {queue.dir}", file=sys.stderr)
            return 1
        tr = spec.get("trace") or {}
        if not tr.get("trace_id"):
            print(f"job {args.job_id} has no trace (submitted with "
                  f"tracing disabled?)", file=sys.stderr)
            return 1
        spans = obs.merge_trace(queue.obs_dir(), tr["trace_id"])
        tree = obs.span_tree(spans)
        out_path = args.out or f"TRACE_{args.job_id}.json"
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(obs.chrome_trace(spans), f)
        print(f"trace {tr['trace_id']}: {len(spans)} spans "
              f"({len(tree['roots'])} roots, {len(tree['orphans'])} orphans) "
              f"-> {out_path}")
        return 0

    if args.cmd == "analyze":
        from repro.api import analyze

        res = analyze(args.dataset_path, auto=args.auto,
                      limit=args.limit or None)
        print(f"n={res['n']} ops={','.join(res['ops'])}")
        for k, st in sorted(res["numeric"].items()):
            print(f"  {k:24s} mean={st.mean:.3f} p50={st.p50:.3f} p95={st.p95:.3f}")
        for k, counts in sorted(res["tags"].items()):
            top = sorted(counts.items(), key=lambda kv: -kv[1])[:5]
            print(f"  {k:24s} " + " ".join(f"{t}:{c}" for t, c in top))
        return 0
    return 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
