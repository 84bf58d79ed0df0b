"""kubedl-tpu CLI — run jobs locally or serve the operator.

    python -m kubedl_tpu.cli run -f examples/tf_job_mnist.yaml
    python -m kubedl_tpu.cli operator --metrics-port 8443 --workloads '*'
    python -m kubedl_tpu.cli validate -f job.yaml

Flag names keep parity with the reference's startup flags
(ref main.go:54-66, docs/startup_flags.md): --max-reconciles,
--gang-scheduler-name, --workloads; TPU-native additions: --tpu-slices.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import yaml

from kubedl_tpu.api.common import is_failed, is_succeeded
from kubedl_tpu.api.validation import ValidationError, validate as api_validate
from kubedl_tpu.core.leader import DEFAULT_LEASE_PATH, data_root
from kubedl_tpu.core.store import NotFound
from kubedl_tpu.operator import Operator, OperatorConfig
from kubedl_tpu.server import OperatorHTTPServer


def _load_manifests(path: str):
    with open(path) as f:
        return [m for m in yaml.safe_load_all(f) if m]


def _kv_pairs(entries, value_type, flag, minimum=None, exclusive=False):
    """Parse repeated NAME=VALUE flags (--tenant-weight / --tenant-cap),
    rejecting out-of-range values at startup — a negative weight would
    silently corrupt every tenant's fair share (sched/quota.py)."""
    out = {}
    for entry in entries or []:
        name, sep, val = entry.partition("=")
        if not sep or not name:
            raise SystemExit(f"error: {flag} expects NAME=VALUE, got {entry!r}")
        try:
            out[name] = value_type(val)
        except ValueError:
            raise SystemExit(f"error: {flag} {entry!r}: bad value {val!r}")
        if isinstance(out[name], float) and not math.isfinite(out[name]):
            # nan compares False against any bound below and would
            # poison every tenant's computed fair share downstream
            raise SystemExit(f"error: {flag} {entry!r}: value must be finite")
        if minimum is not None and (
            out[name] <= minimum if exclusive else out[name] < minimum
        ):
            bound = f"> {minimum}" if exclusive else f">= {minimum}"
            raise SystemExit(f"error: {flag} {entry!r}: value must be {bound}")
    return out


def _mk_operator(args) -> Operator:
    return Operator(
        OperatorConfig(
            max_reconciles=args.max_reconciles,
            enable_gang_scheduling=bool(args.tpu_slices) or args.gang,
            gang_scheduler_name=args.gang_scheduler_name,
            tpu_slices=args.tpu_slices,
            scheduler_policy=args.scheduler_policy,
            tenant_weights=_kv_pairs(args.tenant_weight, float, "--tenant-weight",
                                     minimum=0, exclusive=True),
            tenant_caps=_kv_pairs(args.tenant_cap, int, "--tenant-cap",
                                  minimum=0),
            enable_preemption=not args.disable_preemption,
            enable_elastic=not args.disable_elastic,
            workloads=args.workloads,
            object_storage=args.object_storage,
            event_storage=args.event_storage,
            storage_db_path=args.storage_db_path,
            enable_leader_election=getattr(args, "enable_leader_election", False),
            leader_lease_path=getattr(args, "leader_lease_path", DEFAULT_LEASE_PATH),
            leader_lease_duration=getattr(args, "leader_lease_duration", 15.0),
            leader_renew_period=getattr(args, "leader_renew_period", 5.0),
            leader_retry_period=getattr(args, "leader_retry_period", 2.0),
            journal_dir=getattr(args, "journal_dir", ""),
            journal_compact_bytes=getattr(
                args, "journal_compact_bytes", 1024 * 1024),
            history_dir=getattr(args, "history_dir", ""),
            history_retention_max_age_s=getattr(
                args, "history_retention_age", 0.0),
            history_retention_max_bytes=getattr(
                args, "history_retention_bytes", 0),
            kube_api_url=getattr(args, "kube_api_url", ""),
            kube_namespace=getattr(args, "kube_namespace", "default"),
        )
    )


# ---------------------------------------------------------------------------
# client commands (kubectl-style, against a running `operator` server)
# ---------------------------------------------------------------------------


def _client_request(args, method: str, path: str, body=None):
    import urllib.error
    import urllib.request

    url = args.server.rstrip("/") + path
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    token = args.api_token or os.environ.get("KUBEDL_API_TOKEN", "")
    if token:
        req.add_header("Authorization", f"Bearer {token}")
    if data is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=15) as r:
            ctype = r.headers.get("Content-Type", "")
            raw = r.read().decode()
    except urllib.error.HTTPError as e:
        print(f"error: HTTP {e.code}: {e.read().decode()}", file=sys.stderr)
        return None
    except urllib.error.URLError as e:
        print(f"error: cannot reach {url}: {e.reason}", file=sys.stderr)
        return None
    return json.loads(raw) if ctype.startswith("application/json") else raw


def _job_phase(status) -> str:
    """Latest True condition type — the kubectl STATUS column."""
    for c in reversed((status or {}).get("conditions") or []):
        if str(c.get("status", "")).lower() in ("true", "1"):
            return str(c.get("type", "Unknown"))
    return "Pending"


def _format_row(row, widths) -> str:
    return "".join(str(c).ljust(widths[i]) for i, c in enumerate(row)).rstrip()


def _grow_widths(widths, row) -> None:
    """Widen columns for a continuation row longer than anything in the
    initial snapshot, so later rows stay aligned with each other."""
    for i, cell in enumerate(row):
        if i < len(widths):
            widths[i] = max(widths[i], len(str(cell)) + 2)


def _span_detail(attrs) -> str:
    """The DETAIL column of `trace`: the attributes that say what a span
    was, of a looped stack's step its passes and exit distribution (the
    `loop_*` counters), of a state-space model's step what its scans
    carried (the `ssm_*` counters), of a model with convolution layers
    how many of them ran as kernels (`short_conv_*`), of a several-stream
    model's step how its streams mixed (the `hc_*` counters), of a step
    of gated or per-layer-RoPE attention its layer kinds and mean gate
    (the `attn_*` counters) and of a multi-token prediction module's step
    its two losses (docs/observability.md)."""
    detail = [f"{k}={attrs[k]}" for k in
              ("step", "stage", "cause", "outcome", "shape", "reason", "error",
               "fun", "cache")
              if k in attrs]
    if "loop_passes" in attrs:
        passes = int(attrs["loop_passes"])
        detail.append(f"passes={passes}")
        detail.append("exit=" + "/".join(
            f"{attrs.get(f'loop_exit_mass_{t}', 0.0):.2f}"
            for t in range(1, passes + 1)))
    if "ssm_layers" in attrs:
        detail.append(f"ssm_layers={int(attrs['ssm_layers'])}")
        detail.append(f"chunks={int(attrs['ssm_chunks'])}")
        detail.append(f"carry={attrs['ssm_state_carry']:.3f}")
        detail.append(f"dt={attrs['ssm_dt_mean']:.4f}")
        if "ssm_kernel_chunks" in attrs:  # a record from before the kernels has none
            detail.append(f"kernel_chunks={int(attrs['ssm_kernel_chunks'])}")
        if "ssm_conv_kernel_layers" in attrs:
            detail.append(
                f"conv_kernel_layers={int(attrs['ssm_conv_kernel_layers'])}")
    if "short_conv_layers" in attrs:
        detail.append(f"short_conv_layers={int(attrs['short_conv_layers'])}")
        detail.append(
            f"short_conv_kernel_layers={int(attrs['short_conv_kernel_layers'])}")
    if "hc_mappings" in attrs:
        detail.append(f"hc_mappings={int(attrs['hc_mappings'])}")
        detail.append(f"offdiag={attrs['hc_res_offdiag']:.3f}")
        detail.append(f"sinkhorn_residual={attrs['hc_sinkhorn_residual']:.1e}")
        detail.append(f"pre={attrs['hc_pre_mean']:.3f}")
        detail.append(f"post={attrs['hc_post_mean']:.3f}")
        if "hc_kernel_mappings" in attrs:  # a record from before the kernels has none
            detail.append(f"kernel_mappings={int(attrs['hc_kernel_mappings'])}")
    if "attn_windowed_layers" in attrs:
        detail.append(f"windowed_layers={int(attrs['attn_windowed_layers'])}")
        detail.append(f"nope_layers={int(attrs['attn_nope_layers'])}")
        if "attn_gate_mean" in attrs:
            detail.append(f"gate={attrs['attn_gate_mean']:.3f}")
    if "mtp_ce" in attrs:
        detail.append(f"ce={attrs['ce']:.4f}")
        detail.append(f"mtp_ce={attrs['mtp_ce']:.4f}")
        detail.append(f"mtp_positions={int(attrs['mtp_positions'])}")
    return " ".join(detail)


def _print_table(rows):
    """Print aligned rows; returns the column widths so continuation rows
    (watch mode) can keep the alignment."""
    if not rows:
        return []
    widths = [max(len(str(r[i])) for r in rows) + 2 for i in range(len(rows[0]))]
    for r in rows:
        print(_format_row(r, widths), flush=True)
    return widths


def cmd_get(args) -> int:
    if args.name:
        if getattr(args, "watch", False):
            print("error: -w/--watch applies to the list form "
                  f"(kubedl-tpu get {args.kind} -w)", file=sys.stderr)
            return 2
        obj = _client_request(
            args, "GET", f"/apis/{args.kind}/{args.namespace}/{args.name}"
        )
        if obj is None:
            return 1
        print(json.dumps(obj, indent=2, default=str))
        return 0

    def snapshot():
        listing = _client_request(args, "GET", f"/apis/{args.kind}")
        if listing is None:
            return None
        rows = []
        for item in listing.get("items", []):
            meta = item.get("metadata") or {}
            if not args.all_namespaces and meta.get("namespace") != args.namespace:
                continue
            rows.append((meta.get("namespace", ""), meta.get("name", ""),
                         _job_phase(item.get("status"))))
        return rows

    rows = snapshot()
    if rows is None:
        return 1
    header = ("NAMESPACE", "NAME", "STATUS")
    widths = _print_table([header] + rows)
    if not getattr(args, "watch", False):
        return 0
    # kubectl -w: poll and print rows whose status changed, appeared, or
    # were deleted, keeping the initial table's column alignment; each
    # row flushes so piped output streams. Transient request failures
    # are retried a few times before giving up. KUBEDL_WATCH_MAX bounds
    # the loop for tests; default runs until interrupted.
    seen = dict(((ns, name), st) for ns, name, st in rows)
    max_polls = int(os.environ.get("KUBEDL_WATCH_MAX", "0"))
    polls = failures = 0
    try:
        while not max_polls or polls < max_polls:
            time.sleep(float(os.environ.get("KUBEDL_WATCH_INTERVAL", "2")))
            polls += 1
            rows = snapshot()
            if rows is None:
                failures += 1
                if failures >= 3:
                    print("error: watch lost the server (3 consecutive "
                          "failures)", file=sys.stderr)
                    return 1
                continue
            failures = 0
            current = set()
            for ns, name, st in rows:
                current.add((ns, name))
                if seen.get((ns, name)) != st:
                    seen[(ns, name)] = st
                    _grow_widths(widths, (ns, name, st))
                    print(_format_row((ns, name, st), widths), flush=True)
            for key in sorted(set(seen) - current):
                del seen[key]
                _grow_widths(widths, (key[0], key[1], "Deleted"))
                print(_format_row((key[0], key[1], "Deleted"), widths),
                      flush=True)
    except KeyboardInterrupt:
        pass
    return 0


def cmd_apply(args) -> int:
    rc = 0
    for path in args.files:
        for manifest in _load_manifests(path):
            kind = manifest.get("kind", "")
            out = _client_request(args, "POST", f"/apis/{kind}", body=manifest)
            if out is None:
                rc = 1
                continue
            meta = out.get("metadata") or {}
            print(f"applied {kind} {meta.get('namespace')}/{meta.get('name')}")
    return rc


def cmd_delete(args) -> int:
    out = _client_request(
        args, "DELETE", f"/apis/{args.kind}/{args.namespace}/{args.name}"
    )
    if out is None:
        return 1
    print(f"deleted {args.kind} {args.namespace}/{args.name}")
    return 0


def cmd_logs(args) -> int:
    path = f"/logs/{args.namespace}/{args.pod}"
    params = []
    if args.container:
        params.append(f"container={args.container}")
    if args.tail is not None:
        params.append(f"tail={args.tail}")
    if params:
        path += "?" + "&".join(params)
    out = _client_request(args, "GET", path)
    if out is None:
        return 1
    sys.stdout.write(out if isinstance(out, str) else str(out))
    return 0


def cmd_describe(args) -> int:
    """kubectl-describe-style view of one job: metadata, replica specs,
    the condition machine's history, replica statuses, and the job's
    events — the triage view `get` (one JSON blob) doesn't give."""
    obj = _client_request(
        args, "GET", f"/apis/{args.kind}/{args.namespace}/{args.name}")
    if obj is None:
        return 1
    meta = obj.get("metadata") or {}
    spec = obj.get("spec") or {}
    status = obj.get("status") or {}
    print(f"Name:      {meta.get('name', '')}")
    print(f"Namespace: {meta.get('namespace', '')}")
    print(f"Kind:      {obj.get('kind', args.kind)}")
    print(f"Created:   {meta.get('creationTimestamp', '')}")
    print(f"Status:    {_job_phase(status)}")
    replica_key = next((k for k in spec if k.endswith("ReplicaSpecs")), None)
    if replica_key:
        print("Replicas:")
        for rtype, rspec in sorted((spec.get(replica_key) or {}).items()):
            rstat = (status.get("replicaStatuses") or {}).get(rtype) or {}
            print(f"  {rtype}: {rspec.get('replicas', 1)} desired | "
                  f"{rstat.get('active', 0)} active, "
                  f"{rstat.get('succeeded', 0)} succeeded, "
                  f"{rstat.get('failed', 0)} failed "
                  f"(restart {rspec.get('restartPolicy', '')})")
    conds = status.get("conditions") or []
    if conds:
        print("Conditions:")
        rows = [("TYPE", "STATUS", "REASON", "LAST TRANSITION", "MESSAGE")]
        for c in conds:
            rows.append((c.get("type", ""), c.get("status", ""),
                         c.get("reason", ""),
                         c.get("lastTransitionTime", ""),
                         c.get("message", "")))
        _print_table(rows)
    listing = _client_request(args, "GET", f"/events/{args.namespace}")
    if listing is not None:
        kind = obj.get("kind") or args.kind
        rows = _event_rows(listing, only_kind=kind, only_name=args.name,
                           with_object=False)
        if len(rows) > 1:
            print("Events:")
            _print_table(rows)
    return 0


def _event_rows(listing, only_kind=None, only_name=None, with_object=True):
    """Shared event-table builder for `events` (all objects) and
    `describe` (one object: kind AND name must match — a same-named
    object of another kind must not pollute the triage view)."""
    header = (("TYPE", "REASON", "OBJECT", "COUNT", "MESSAGE")
              if with_object else ("TYPE", "REASON", "COUNT", "MESSAGE"))
    rows = [header]
    for e in listing.get("items", []):
        inv = e.get("involvedObject") or e.get("involved_object") or {}
        if only_name is not None and inv.get("name") != only_name:
            continue
        if (only_kind is not None
                and (inv.get("kind") or "").lower() != only_kind.lower()):
            continue
        row = [e.get("type", ""), e.get("reason", "")]
        if with_object:
            row.append(f"{inv.get('kind', '')}/{inv.get('name', '')}")
        row += [e.get("count", 1), e.get("message", "")]
        rows.append(tuple(row))
    return rows


def cmd_events(args) -> int:
    listing = _client_request(args, "GET", f"/events/{args.namespace}")
    if listing is None:
        return 1
    _print_table(_event_rows(listing))
    return 0


def cmd_top(args) -> int:
    """kubectl-top-style view of the operator: TPU slice pool utilization
    plus per-controller reconcile health (from /debug/vars)."""
    vars_ = _client_request(args, "GET", "/debug/vars")
    if vars_ is None:
        return 1
    pool = vars_.get("slice_pool")
    if pool:
        print(f"slice pool: {pool['chips_reserved']}/{pool['chips_total']} chips "
              f"reserved ({pool['utilization']:.0%}), "
              f"{pool['slices_reserved']}/{pool['slices_total']} slices")
        rows = [("SLICE", "TYPE", "CHIPS", "RESERVED BY")]
        for s in pool.get("slices", []):
            rows.append((s["name"], s["type"], s.get("chips", ""),
                         s.get("reserved_by") or "-"))
        _print_table(rows)
        print()
    cap = vars_.get("capacity")
    if cap:
        _print_capacity_tenants(cap)
        print()
    gp = vars_.get("goodput")
    if gp and gp.get("jobs"):
        # RL-fleet columns render only when some job has them — the
        # table stays narrow for training/serving-only operators
        has_rl = any(
            (rec.get("buckets") or {}).get(k)
            for rec in gp["jobs"].values()
            for k in ("rollout", "actor_starved", "learner_starved",
                      "weight_sync"))
        header = ["JOB", "GOODPUT", "WALL_S", "STEPS_S", "QUEUE_S", "INIT_S",
                  "CKPT_S", "RESHARD_S", "EVICT_S"]
        if has_rl:
            header += ["ROLLOUT_S", "ASTARVE_S", "LSTARVE_S", "WSYNC_S"]
        rows = [tuple(header + ["OTHER_S"])]
        for job, rec in sorted(gp["jobs"].items()):
            b = rec.get("buckets") or {}
            row = [
                job, f"{rec.get('ratio', 0.0):.0%}",
                f"{rec.get('wall_s', 0.0):.2f}",
                f"{b.get('steps', 0.0):.2f}", f"{b.get('queue_wait', 0.0):.2f}",
                f"{b.get('init_compile', 0.0):.2f}",
                f"{b.get('checkpoint', 0.0):.2f}",
                f"{b.get('reshard', 0.0):.2f}", f"{b.get('eviction', 0.0):.2f}",
            ]
            if has_rl:
                row += [f"{b.get('rollout', 0.0):.2f}",
                        f"{b.get('actor_starved', 0.0):.2f}",
                        f"{b.get('learner_starved', 0.0):.2f}",
                        f"{b.get('weight_sync', 0.0):.2f}"]
            rows.append(tuple(row + [f"{b.get('other', 0.0):.2f}"]))
        _print_table(rows)
        print()
    rl = vars_.get("rl")
    if rl and rl.get("jobs"):
        rows = [("RL_JOB", "QUEUE", "WLAG", "PRODUCED", "CONSUMED",
                 "STALE_DROP", "STEPS", "STEP_MS", "LOSS")]
        for job, rec in sorted(rl["jobs"].items()):
            rows.append((
                job, rec.get("queue_depth", 0), rec.get("weight_lag", 0),
                rec.get("produced", 0), rec.get("consumed", 0),
                rec.get("stale_dropped", 0), rec.get("learn_steps", 0),
                f"{rec.get('learn_step_s', 0.0) * 1e3:.1f}",
                (f"{rec['loss']:.4f}" if "loss" in rec else "-"),
            ))
        _print_table(rows)
        print()
    weights = vars_.get("weights")
    if weights and weights.get("jobs"):
        rows = [("WEIGHTS_JOB", "VERSION", "PUBLISHED", "CHUNKS",
                 "BYTES", "REPARENTS", "PODS_COMMITTED")]
        for job, rec in sorted(weights["jobs"].items()):
            pods = rec.get("pods") or {}
            version = rec.get("published_version", 0)
            committed = sum(1 for v in pods.values() if v >= version)
            rows.append((
                job, version, rec.get("versions_published", 0),
                rec.get("chunks_relayed", 0), rec.get("bytes_total", 0),
                rec.get("reparents", 0),
                f"{committed}/{len(pods)}" if pods else "-",
            ))
        _print_table(rows)
        print()
    steps = vars_.get("steps")
    if steps and steps.get("jobs"):
        rows = [("STEP_JOB", "PODS", "MEDIAN_STEP_MS", "STRAGGLERS",
                 "COMPILES")]
        for job, rec in sorted(steps["jobs"].items()):
            rows.append((
                job, len(rec.get("pods") or {}),
                f"{rec.get('median_step_s', 0.0) * 1e3:.1f}",
                ",".join(rec.get("stragglers") or []) or "-",
                rec.get("compile_events", 0),
            ))
        _print_table(rows)
        print()
    pipe = vars_.get("pipeline")
    if pipe and pipe.get("jobs"):
        rows = [("PIPELINE_JOB", "SCHEDULE", "STAGES", "BUBBLE", "STEPS",
                 "STAGE_STEP_MS")]
        for job, rec in sorted(pipe["jobs"].items()):
            per_stage = " ".join(
                f"{s}:{t * 1e3:.0f}" for s, t in
                # /debug/vars JSON turns the int stage keys into strings;
                # sort numerically or stage 10 renders before stage 2
                sorted((rec.get("stage_step_s") or {}).items(),
                       key=lambda kv: int(kv[0])))
            rows.append((job, rec.get("schedule", ""), rec.get("stages", 0),
                         f"{rec.get('bubble_frac', 0.0):.3f}",
                         rec.get("steps", 0), per_stage or "-"))
        _print_table(rows)
        print()
    rows = [("CONTROLLER", "RECONCILES", "ERRORS", "REQUEUES", "QUEUE", "MEAN_MS")]
    for name, c in sorted((vars_.get("controllers") or {}).items()):
        rows.append((name, c.get("reconciles", 0), c.get("errors", 0),
                     c.get("requeues", 0), c.get("queue_depth", ""),
                     round(c.get("mean_seconds", 0.0) * 1e3, 2)))
    _print_table(rows)
    return 0


def _print_capacity_tenants(cap) -> None:
    print(f"capacity scheduler: policy={cap.get('policy')} "
          f"preemptions={cap.get('preemptions_total', 0)} "
          f"resizes={cap.get('resizes_total', 0)}")
    reshards = cap.get("reshards_total")
    if reshards is not None:
        downtime = cap.get("resize_downtime") or {}
        n = downtime.get("count", 0)
        mean = (downtime.get("sum", 0.0) / n) if n else 0.0
        print(f"live reshards: ok={reshards.get('ok', 0)} "
              f"staged={reshards.get('staged', 0)} "
              f"fallback={reshards.get('fallback', 0)} "
              f"failed={reshards.get('failed', 0)} "
              f"pending={cap.get('reshards_pending', 0)} "
              f"downtime last={downtime.get('last', 0.0):.2f}s "
              f"mean={mean:.2f}s")
    rows = [("TENANT", "WEIGHT", "CHIPS", "FAIR_SHARE", "SHARE", "CAP",
             "CHIP_S", "PREEMPTED")]
    for tenant, t in sorted((cap.get("tenants") or {}).items()):
        cap_chips = t.get("cap_chips")
        rows.append((
            tenant, t.get("weight", 1.0), t.get("chips_in_use", 0),
            t.get("fair_share_chips", 0.0),
            f"{t.get('share', 0.0):.0%}",
            cap_chips if cap_chips is not None else "-",
            t.get("chip_seconds", 0.0), t.get("preemptions", 0),
        ))
    _print_table(rows)


def cmd_queue(args) -> int:
    """Capacity-scheduler view: the gang queue (who runs, who waits, at
    what shape) plus per-tenant quota state — the triage surface for
    "why isn't my job scheduled"."""
    vars_ = _client_request(args, "GET", "/debug/vars")
    if vars_ is None:
        return 1
    cap = vars_.get("capacity")
    if not cap:
        print("capacity scheduler not enabled (start the operator with "
              "--scheduler-policy)", file=sys.stderr)
        return 1
    _print_capacity_tenants(cap)
    print()
    rows = [("GANG", "TENANT", "PRIO", "SHAPE", "STATE", "SLICES",
             "DRAINING", "WAIT_S", "PREEMPTED")]
    for q in cap.get("queue", []):
        rows.append((
            q.get("gang", ""), q.get("tenant", ""), q.get("priority", 0),
            q.get("shape", ""), q.get("state", ""),
            ",".join(q.get("slices") or []) or "-",
            ",".join(q.get("draining") or []) or "-",
            q.get("waiting_seconds", 0.0), q.get("preemptions", 0),
        ))
    _print_table(rows)
    return 0


def cmd_trace(args) -> int:
    """Flight-recorder view of one job (docs/observability.md): the
    merged cross-plane span timeline, the goodput breakdown computed from
    the same spans, and optional Chrome-trace export for Perfetto.
    Reads the operator's /trace endpoint, or a trace dir directly with
    --dir (offline evidence, e.g. a committed bench artifact)."""
    from kubedl_tpu.obs import chrome_trace, goodput, load_spans

    if args.dir:
        spans = load_spans(args.dir)
        gp = goodput(spans)
        trace_ids = gp.get("trace_ids") or []
    else:
        out = _client_request(
            args, "GET", f"/trace/{args.namespace}/{args.job}")
        if out is None:
            return 1
        spans = out.get("spans") or []
        gp = out.get("goodput") or goodput(spans)
        trace_ids = [out.get("trace_id", "")]
    if not spans:
        print(f"no spans recorded for {args.namespace}/{args.job}",
              file=sys.stderr)
        return 1
    if args.chrome_trace:
        with open(args.chrome_trace, "w") as f:
            json.dump(chrome_trace(spans), f)
        print(f"chrome trace ({len(spans)} spans) written to "
              f"{args.chrome_trace} — load in Perfetto / chrome://tracing")
    t0 = gp.get("t0") or min(s.get("ts", 0.0) for s in spans)
    print(f"trace {args.job}: {len(spans)} spans, "
          f"wall {gp.get('wall_s', 0.0):.3f}s, "
          f"trace_id {' '.join(trace_ids) or '?'}")
    rows = [("T+S", "DUR_S", "SERVICE", "SPAN", "DETAIL")]
    for s in spans:
        detail = _span_detail(s.get("attrs") or {})
        rows.append((
            f"{s.get('ts', 0.0) - t0:+.3f}",
            f"{s.get('dur', 0.0):.3f}",
            s.get("service", ""), s.get("name", ""), detail or "-"))
    _print_table(rows)
    print()
    print(f"goodput: {gp.get('ratio', 0.0):.1%} "
          f"(productive step time / wall time)")
    rows = [("BUCKET", "SECONDS", "SHARE")]
    wall = gp.get("wall_s", 0.0) or 1.0
    for bucket, secs in (gp.get("buckets") or {}).items():
        rows.append((bucket, f"{secs:.3f}", f"{secs / wall:.1%}"))
    _print_table(rows)
    _print_compile_table(spans)
    return 0


def _print_compile_table(spans) -> None:
    """Under the goodput table: one row a `jax.compile` span, a function
    compiled as JAX reported it (obs/compiles.py: tracing, the conversion
    to MLIR, the backend's compile or the persistent cache's read), with
    the step whose record covers it, and under them the inner functions
    the longest trace spent most of its own time in."""
    compiled = [s for s in spans if s.get("name") == "jax.compile"]
    if not compiled:
        return
    steps = [s for s in spans if s.get("name") in
             ("train.compile", "train.step", "pipeline.step")]

    def step_of(span) -> str:
        for st in steps:
            if (st.get("service") == span.get("service")
                    and st["ts"] <= span["ts"] <= st["ts"] + st.get("dur", 0.0)):
                return str(st.get("attrs", {}).get("step", "?"))
        return "-"

    print()
    print("compiles (as jax.monitoring reported them):")
    rows = [("FUN", "SERVICE", "TRACE_S", "LOWER_S", "EXECUTABLE_S", "CACHE",
             "CACHE_READ_S", "STEP")]
    for s in compiled:
        a = s.get("attrs") or {}
        rows.append((a.get("fun", "?"), s.get("service", ""),
                     f"{a.get('trace_s', 0.0):.3f}", f"{a.get('lower_s', 0.0):.3f}",
                     f"{s.get('dur', 0.0):.3f}", a.get("cache", "?"),
                     f"{a.get('cache_read_s', 0.0):.3f}", step_of(s)))
    _print_table(rows)
    traced = [s for s in spans if s.get("name") == "jax.trace"
              and (s.get("attrs") or {}).get("nested")]
    if not traced:
        return
    longest = max(traced, key=lambda s: s.get("dur", 0.0))
    print()
    print(f"inner functions of {longest['attrs'].get('fun', '?')}'s trace "
          f"({longest.get('dur', 0.0):.3f}s), by their own tracing time:")
    rows = [("FUN", "CALLS", "OWN_S", "TOTAL_S")]
    for n in longest["attrs"]["nested"]:
        rows.append((n.get("fun", "?"), n.get("calls", 0),
                     f"{n.get('own_s', 0.0):.3f}", f"{n.get('total_s', 0.0):.3f}"))
    _print_table(rows)


def cmd_history(args) -> int:
    """Fleet history view of one job (docs/ha.md): the last trace
    snapshot + goodput the history store captured, the lifecycle
    markers, and the job/event rows the storage backends persisted —
    still answerable after both the CRD (TTL) and the trace dir are
    gone, which is when `kubedl-tpu trace` starts returning 404."""
    out = _client_request(
        args, "GET", f"/history/{args.namespace}/{args.job}")
    if out is None:
        return 1
    spans = out.get("spans") or []
    gp = out.get("goodput") or {}
    print(f"history {args.namespace}/{args.job}: {len(spans)} spans "
          f"snapshotted, goodput {gp.get('ratio', 0.0):.1%}")
    job = out.get("job_record")
    if job:
        print(f"job record: kind={job.get('kind') or '?'} "
              f"status={job.get('status') or '?'} "
              f"deleted={bool(job.get('deleted'))} "
              f"created={job.get('gmt_created') or '?'} "
              f"finished={job.get('gmt_finished') or '?'}")
    lifecycle = out.get("lifecycle") or []
    if lifecycle:
        rows = [("EVENT", "DETAIL")]
        for rec in lifecycle:
            detail = " ".join(
                f"{k}={rec[k]}" for k in sorted(rec)
                if k not in ("k", "kind", "t", "event"))
            rows.append((rec.get("event", "?"), detail or "-"))
        _print_table(rows)
    events = out.get("events") or []
    if events:
        rows = [("TYPE", "REASON", "COUNT", "MESSAGE")]
        for e in events:
            rows.append((e.get("type", ""), e.get("reason", ""),
                         e.get("count", 1), e.get("message", "")))
        _print_table(rows)
    return 0


def cmd_analyze(args) -> int:
    """Fleet invariant analyzer (docs/static_analysis.md): run the AST
    lint passes + lock-order analysis and print the report — the same
    gate `make lint`/presubmit runs, inspectable like `top`/`trace`."""
    from kubedl_tpu.analysis.__main__ import main as analysis_main

    argv = []
    if args.json:
        argv.append("--json")
    if args.no_tests:
        argv.append("--no-tests")
    if args.show_allowlisted:
        argv.append("--show-allowlisted")
    if args.list_passes:
        argv.append("--list-passes")
    if args.only:
        argv += ["--only", args.only]
    if args.model:
        argv.append("--model")
    if args.root:
        argv += ["--root", args.root]
    return analysis_main(argv)


def cmd_run(args) -> int:
    op = _mk_operator(args)
    op.register_all()
    op.start()
    server = None
    if args.metrics_port:
        server = OperatorHTTPServer(op, port=args.metrics_port)
        port = server.start()
        print(f"serving metrics/API on http://127.0.0.1:{port}")
    rc = 0
    try:
        jobs = [op.apply(m) for p in args.files for m in _load_manifests(p)]
        for job in jobs:
            print(f"applied {job.kind} {job.metadata.namespace}/{job.metadata.name}")
        deadline = time.monotonic() + args.timeout
        pending = {(j.kind, j.metadata.namespace, j.metadata.name) for j in jobs}
        last_report = 0.0
        while pending and time.monotonic() < deadline:
            for key in list(pending):
                kind, ns, name = key
                try:
                    fresh = op.store.get(kind, ns, name)
                except NotFound:
                    print(f"{kind} {ns}/{name}: deleted before completion")
                    pending.discard(key)
                    rc = 1
                    continue
                if is_succeeded(fresh.status):
                    print(f"{kind} {ns}/{name}: Succeeded")
                    pending.discard(key)
                elif is_failed(fresh.status):
                    cond = fresh.status.conditions[-1]
                    print(f"{kind} {ns}/{name}: Failed — {cond.message}")
                    pending.discard(key)
                    rc = 1
            if time.monotonic() - last_report > 5:
                last_report = time.monotonic()
                for kind, ns, name in pending:
                    phases = [
                        (p.metadata.name, p.status.phase.value)
                        for p in op.store.list("Pod", namespace=ns)
                        if p.metadata.labels.get("job-name") == name
                    ]
                    print(f"waiting on {kind} {ns}/{name}: pods={phases}")
            time.sleep(0.1)
        if pending:
            print(f"timed out waiting for: {sorted(pending)}")
            rc = 1
    finally:
        if server:
            server.stop()
        op.stop()
    return rc


def cmd_operator(args) -> int:
    op = _mk_operator(args)
    op.register_all()
    # Construct the server BEFORE op.start(): its token validation can
    # raise (non-loopback bind without a token), and failing here must not
    # leave a leader lease held or manager threads running.
    server = OperatorHTTPServer(
        op, host=args.bind, port=args.metrics_port or 8443,
        token=getattr(args, "api_token", None),
    )
    if args.enable_leader_election:
        print(f"acquiring leadership lease at {args.leader_lease_path} ...")
    op.start()
    if op.elector is not None:
        print(f"elected leader as {op.elector.identity}")
    port = server.start()
    print(f"kubedl-tpu operator serving on http://{args.bind}:{port} "
          f"(kinds: {sorted(op.reconcilers)})")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        op.stop()
    return 0


def cmd_webhook(args) -> int:
    """Serve admission webhooks until interrupted (docs/kubernetes.md)."""
    from kubedl_tpu.k8s.webhook import AdmissionWebhookServer

    srv = AdmissionWebhookServer(
        bind=args.bind, port=args.port,
        certfile=args.tls_cert or None, keyfile=args.tls_key or None,
    ).start()
    scheme = "https" if args.tls_cert else "http"
    print(f"admission webhook on {scheme}://{args.bind}:{srv.port} "
          f"(/validate /mutate /healthz)", flush=True)
    try:
        import signal as _signal

        _signal.pause()
    except (KeyboardInterrupt, AttributeError):
        pass
    finally:
        srv.stop()
    return 0


def cmd_validate(args) -> int:
    op = _mk_operator(args)
    op.register_all()
    rc = 0
    for path in args.files:
        for m in _load_manifests(path):
            kind = m.get("kind", "")
            canonical = op._kind_by_lower.get(kind.lower())
            if canonical is None:
                print(f"{path}: unknown kind {kind!r}")
                rc = 1
                continue
            engine = op.reconcilers[canonical]
            from kubedl_tpu.utils.serde import from_dict

            job = from_dict(engine.controller.job_type(), m)
            engine.controller.set_defaults(job)
            try:
                api_validate(job, engine.controller)
            except ValidationError as e:
                print(f"{path}: INVALID — {e}")
                rc = 1
                continue
            n = sum(int(s.replicas or 0) for s in engine.controller.replica_specs(job).values())
            print(f"{path}: {canonical} {job.metadata.name} ok ({n} replicas)")
    return rc


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="kubedl-tpu")
    parser.add_argument("--max-reconciles", type=int, default=1)
    parser.add_argument("--workloads", default="*")
    parser.add_argument("--gang-scheduler-name", default="tpu-slice")
    parser.add_argument("--gang", action="store_true", help="enable gang scheduling")
    parser.add_argument("--tpu-slices", nargs="*", default=[],
                        help="TPU pool, e.g. v5e-8 v5p-32")
    # capacity scheduler (docs/scheduling.md): tenant fair-share,
    # preemption, elastic resize over the slice pool
    parser.add_argument("--scheduler-policy", default="",
                        choices=["", "fifo", "priority", "fair_share", "gavel"],
                        help="enable the capacity scheduler with this policy")
    parser.add_argument("--tenant-weight", action="append", default=[],
                        metavar="TENANT=WEIGHT",
                        help="fair-share weight (repeatable; default 1.0)")
    parser.add_argument("--tenant-cap", action="append", default=[],
                        metavar="TENANT=CHIPS",
                        help="hard chips-in-use ceiling (repeatable)")
    parser.add_argument("--disable-preemption", action="store_true",
                        help="scheduler never evicts running gangs "
                             "(also disables elastic grow, which evicts)")
    parser.add_argument("--disable-elastic", action="store_true",
                        help="scheduler never resizes gangs across their "
                             "declared tpuSliceFallbacks shapes")
    # persistence flags (ref --object-storage/--event-storage, persist_controller.go:30-74)
    parser.add_argument("--object-storage", default="",
                        help="object history backend name, e.g. sqlite")
    parser.add_argument("--event-storage", default="",
                        help="event history backend name, e.g. sqlite")
    parser.add_argument("--storage-db-path", default=":memory:",
                        help="database path for the sqlite backend")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_run = sub.add_parser("run", help="run job manifests to completion locally")
    p_run.add_argument("-f", "--files", nargs="+", required=True)
    p_run.add_argument("--timeout", type=float, default=600.0)
    p_run.add_argument("--metrics-port", type=int, default=0)
    p_run.set_defaults(fn=cmd_run)

    p_op = sub.add_parser("operator", help="serve the operator over HTTP")
    p_op.add_argument("--bind", default="127.0.0.1")
    p_op.add_argument("--metrics-port", type=int, default=8443)
    # ref main.go:56: leader election defaults ON for the deployed operator
    p_op.add_argument("--enable-leader-election", action=argparse.BooleanOptionalAction,
                      default=True)
    p_op.add_argument("--leader-lease-path", default=DEFAULT_LEASE_PATH)
    # kube mode elects on a coordination.k8s.io Lease; client-go-ish timing
    p_op.add_argument("--leader-lease-duration", type=float, default=15.0)
    p_op.add_argument("--leader-renew-period", type=float, default=5.0)
    p_op.add_argument("--leader-retry-period", type=float, default=2.0)
    p_op.add_argument("--kube-api-url", default="",
                      help="reconcile real cluster objects through this "
                           "kube-apiserver ('in-cluster' = service account)")
    p_op.add_argument("--kube-namespace", default="default")
    p_op.add_argument("--api-token", default=None,
                      help="bearer token for the HTTP API (env KUBEDL_API_TOKEN); "
                           "REQUIRED for non-loopback --bind")
    # durable control plane (docs/ha.md): the deployed operator journals
    # and keeps history by default, under the data root (KUBEDL_DATA_DIR)
    p_op.add_argument("--journal-dir",
                      default=os.path.join(data_root(), "journal"),
                      help="write-ahead grant/drain journal dir "
                           "('' disables)")
    p_op.add_argument("--journal-compact-bytes", type=int,
                      default=1024 * 1024,
                      help="compact the journal (snapshot + truncate) "
                           "once it grows past this many bytes "
                           "(0 disables compaction)")
    p_op.add_argument("--history-dir",
                      default=os.path.join(data_root(), "history"),
                      help="fleet history store dir, outlives job TTL "
                           "('' disables)")
    p_op.add_argument("--history-retention-age", type=float, default=0.0,
                      help="prune history records older than this many "
                           "seconds (0 keeps forever)")
    p_op.add_argument("--history-retention-bytes", type=int, default=0,
                      help="prune oldest history records once the log "
                           "grows past this many bytes (0 = unbounded)")
    p_op.set_defaults(fn=cmd_operator)

    p_val = sub.add_parser("validate", help="parse and default manifests")
    p_val.add_argument("-f", "--files", nargs="+", required=True)
    p_val.set_defaults(fn=cmd_validate)

    p_wh = sub.add_parser(
        "webhook",
        help="serve admission webhooks (/validate + /mutate AdmissionReview)",
    )
    p_wh.add_argument("--bind", default="0.0.0.0")
    p_wh.add_argument("--port", type=int, default=9443)
    p_wh.add_argument("--tls-cert", default="",
                      help="TLS cert path (apiserver requires HTTPS)")
    p_wh.add_argument("--tls-key", default="")
    p_wh.set_defaults(fn=cmd_webhook)

    # kubectl-style client commands against a running `operator` server
    def client_parser(name, help_):
        p = sub.add_parser(name, help=help_)
        p.add_argument("--server", default=os.environ.get(
            "KUBEDL_SERVER", "http://127.0.0.1:8443"))
        p.add_argument("--api-token", default=None,
                       help="bearer token (env KUBEDL_API_TOKEN)")
        p.add_argument("-n", "--namespace", default="default")
        return p

    p_get = client_parser("get", "list jobs of a kind, or show one as JSON")
    p_get.add_argument("kind")
    p_get.add_argument("name", nargs="?", default="")
    p_get.add_argument("-A", "--all-namespaces", action="store_true")
    p_get.add_argument("-w", "--watch", action="store_true",
                       help="poll and print status changes until interrupted")
    p_get.set_defaults(fn=cmd_get)

    p_apply = client_parser("apply", "submit manifests to the operator")
    p_apply.add_argument("-f", "--files", nargs="+", required=True)
    p_apply.set_defaults(fn=cmd_apply)

    p_del = client_parser("delete", "delete a job")
    p_del.add_argument("kind")
    p_del.add_argument("name")
    p_del.set_defaults(fn=cmd_delete)

    p_logs = client_parser("logs", "print a pod's container logs")
    p_logs.add_argument("pod")
    p_logs.add_argument("-c", "--container", default="")
    p_logs.add_argument("--tail", type=int, default=None)
    p_logs.set_defaults(fn=cmd_logs)

    p_desc = client_parser(
        "describe", "conditions, replica statuses, and events for one job")
    p_desc.add_argument("kind")
    p_desc.add_argument("name")
    p_desc.set_defaults(fn=cmd_describe)

    p_ev = client_parser("events", "list events in a namespace")
    p_ev.set_defaults(fn=cmd_events)

    p_top = client_parser("top", "slice-pool utilization + controller health")
    p_top.set_defaults(fn=cmd_top)

    p_queue = client_parser(
        "queue", "capacity-scheduler gang queue + tenant quota state")
    p_queue.set_defaults(fn=cmd_queue)

    p_trace = client_parser(
        "trace", "flight-recorder span timeline + goodput for one job")
    p_trace.add_argument("job")
    p_trace.add_argument("--chrome-trace", default="", metavar="OUT.json",
                         help="also export Chrome trace JSON (Perfetto)")
    p_trace.add_argument("--dir", default="",
                         help="read spans from a local trace dir instead "
                              "of the operator server")
    p_trace.set_defaults(fn=cmd_trace)

    p_hist = client_parser(
        "history", "fleet history for one job — outlives job TTL "
                   "(docs/ha.md)")
    p_hist.add_argument("job")
    p_hist.set_defaults(fn=cmd_history)

    p_an = sub.add_parser(
        "analyze",
        help="fleet invariant analyzer: AST lint passes + lock-order "
             "report (docs/static_analysis.md)")
    p_an.add_argument("--json", action="store_true",
                      help="machine-readable report")
    p_an.add_argument("--no-tests", action="store_true",
                      help="skip tests/ (default scope includes it)")
    p_an.add_argument("--show-allowlisted", action="store_true",
                      help="also print pragma-suppressed findings")
    p_an.add_argument("--only", default="",
                      help="comma-separated pass ids to run")
    p_an.add_argument("--list-passes", action="store_true",
                      help="print the registered pass ids and exit")
    p_an.add_argument("--model", action="store_true",
                      help="also run the protocol model checker "
                           "(exhaustive grant/drain/resize exploration)")
    p_an.add_argument("--root", default="",
                      help="repo root (default: auto-detect)")
    p_an.set_defaults(fn=cmd_analyze)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
