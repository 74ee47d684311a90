#!/usr/bin/env python3
"""Drive simtpu's main paths once on the chip, through the entry points a
user calls, and check what comes out.

    python chip_smoke.py            # one TPU chip: phases a, b, c
    python chip_smoke.py --chips 4  # four TPU chips: the node-sharded path

One process holds the chip for the whole run: the CLI runs in-process and
the daemon serves from a thread of this process.  There is no CPU
fallback: without a TPU the script exits non-zero and prints no result.

a. Conformance: `simtpu apply --json` on the three example configs; each
   reports success, 0 unscheduled and an auditor-certified plan.
b. Real size: Kubernetes' documented scale limit ("Considerations for
   large clusters": 5,000 nodes, 150,000 pods) with the bench's plan mix
   (zones, spread, anti-affinity, tolerations, Open-Local storage).  The
   bulk placement `simtpu apply` picks at this size, the serial scan and a
   min-node-add plan all pass the auditor.  Each engine's chip placement
   is byte-equal to the same engine's placement on the host CPU backend
   (the backend the test suite pins against the oracles); the serial scan
   is compared on a prefix of the apps.  The bulk and serial engines are
   not compared with each other: bulk rounds break score ties with
   round-start normalizers (engine/rounds.py), so they legitimately place
   differently.
c. Serve: the daemon answers fit, drain and capacity over HTTP; the fit
   equals the one-shot `simulate()` answer.

Each phase prints its wall time, jit-compile counts and placed/unplaced
counts; the last line is the JSON verdict the driver reads.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Kubernetes' scale limit (kubernetes.io/docs/setup/best-practices/cluster-large)
N_NODES = 5_000
N_PODS = 150_000
SERIAL_PREFIX_DEPLOYMENTS = 10  # serial-scan CPU reference: 10,000 pods

CONFIGS = (
    ("examples/simtpu-config.yaml", ()),
    ("examples/simtpu-gpushare-config.yaml", ("-e", "gpu")),
    ("examples/simtpu-storage-config.yaml", ("-e", "open-local")),
)

FIT_PAYLOAD = {
    "workloads": [{
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {"name": "probe", "namespace": "default"},
        "spec": {
            "replicas": 2,
            "template": {
                "metadata": {"labels": {"app": "probe"}},
                "spec": {"containers": [{
                    "name": "c", "image": "nginx",
                    "resources": {"requests": {"cpu": "1", "memory": "1Gi"}},
                }]},
            },
        },
    }],
}


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or uncertified answer."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Print the phase's wall time and the jit traces it caused."""
    from simtpu.obs.metrics import REGISTRY

    before = REGISTRY.snapshot("compile.")
    t0 = time.perf_counter()
    say(f"[{name}] start")
    yield
    wall = time.perf_counter() - t0
    compiles = {
        k: v for k, v in REGISTRY.delta_since(before).items()
        if k.startswith("compile.") and v
    }
    say(f"[{name}] wall_s={wall} compiles={json.dumps(compiles, sort_keys=True)}")


def phase_b_problem():
    """The 5,000-node x 150,000-pod cluster, seeded; the plan mix of
    bench.py's `time_plan`, and its storage-rich template node."""
    from simtpu.synth import make_node, synth_apps, synth_cluster

    cluster = synth_cluster(
        N_NODES, seed=3, zones=16, taint_frac=0.1, storage_frac=0.09
    )
    apps = synth_apps(
        N_PODS,
        seed=5,
        zones=16,
        pods_per_deployment=1000,
        selector_frac=0.0,
        toleration_frac=0.1,
        anti_affinity_frac=0.2,
        spread_frac=0.3,
        storage_frac=0.25,
        storage_device_frac=0.0,
    )
    template = make_node(
        "tmpl",
        256000,
        512,
        {
            "kubernetes.io/hostname": "tmpl",
            "topology.kubernetes.io/zone": "zone-plan",
        },
        storage_gib=(4000, 4000),
    )
    return cluster, apps, template


def placement(result):
    """(pod name -> node name, sorted (pod name, reason) unscheduled)."""
    nodes = {
        pod["metadata"]["name"]: status.node["metadata"]["name"]
        for status in result.node_status
        for pod in status.pods
    }
    unsched = sorted(
        (u.pod["metadata"]["name"], u.reason) for u in result.unscheduled_pods
    )
    return nodes, unsched


def first_difference(a, b) -> str:
    (na, ua), (nb, ub) = a, b
    for name in sorted(set(na) | set(nb)):
        if na.get(name) != nb.get(name):
            return f"pod {name}: {na.get(name)} vs {nb.get(name)}"
    return f"unscheduled differ: {len(ua)} vs {len(ub)}"


def simulate_seeded(cluster, apps, **kw):
    """One `simulate()` with the pod-name stream reset, so two runs of the
    same batch name their pods alike; audited."""
    from simtpu.api import simulate
    from simtpu.workloads.expand import seed_name_hashes

    seed_name_hashes(7)
    result = simulate(
        cluster, apps, extended_resources=("open-local",), audit=True, **kw
    )
    check(result.audit.ok, f"placement failed its audit: {result.audit}")
    return result


def deployment_prefix(apps, n: int):
    """The first n deployments of synth_apps' one application."""
    from simtpu.core.objects import AppResource, ResourceTypes

    (app,) = apps
    res = ResourceTypes(deployments=list(app.resource.deployments[:n]))
    return [AppResource(name=app.name, resource=res)]


def report_placement(label: str, result) -> None:
    placed = sum(len(s.pods) for s in result.node_status)
    say(
        f"[{label}] placed={placed} unplaced={len(result.unscheduled_pods)} "
        f"audit_ok={result.audit.ok} audit_mode={result.audit.mode}"
    )


def on_host(fn, *args, **kw):
    """Run fn with JAX's default device set to the host CPU: the reference
    the chip's answer must equal."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        return fn(*args, **kw)


def conformance() -> None:
    from simtpu import cli

    for cfg, extra in CONFIGS:
        with phase(f"a:{os.path.basename(cfg)}"):
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                rc = cli.main(["apply", "-f", cfg, "--json", *extra])
            doc = json.loads(out.getvalue())
            audit = doc["engine"].get("audit") or {}
            say(
                f"[a:{os.path.basename(cfg)}] rc={rc} success={doc['success']} "
                f"unscheduled={doc['unscheduled']} audit_ok={audit.get('ok')} "
                f"audit_mode={audit.get('mode')}"
            )
            check(rc == 0, f"{cfg}: exit code {rc}")
            check(doc["success"] is True, f"{cfg}: {doc['message']}")
            check(doc["unscheduled"] == 0, f"{cfg}: pods left unscheduled")
            check(audit.get("ok") is True, f"{cfg}: plan not certified: {audit}")


def real_size() -> None:
    from simtpu.plan.capacity import ApplierOptions, _resolve_engines
    from simtpu.plan.incremental import plan_capacity_incremental
    from simtpu.workloads.expand import seed_name_hashes

    with phase("b:build"):
        cluster, apps, template = phase_b_problem()
        _, bulk, _ = _resolve_engines(ApplierOptions(), cluster, apps)
        check(bulk, "the CLI picks the serial engine at this size")

    with phase("b:bulk"):
        chip = simulate_seeded(cluster, apps, bulk=True)
        report_placement("b:bulk", chip)
    with phase("b:bulk-host-reference"):
        host = on_host(simulate_seeded, cluster, apps, bulk=True)
        a, b = placement(chip), placement(host)
        check(a == b, "bulk placement differs from the host CPU's: "
              + first_difference(a, b))
        say("[b:bulk-host-reference] byte_equal=True")

    with phase("b:serial"):
        serial = simulate_seeded(cluster, apps)
        report_placement("b:serial", serial)
    with phase("b:serial-prefix-host-reference"):
        prefix = deployment_prefix(apps, SERIAL_PREFIX_DEPLOYMENTS)
        a = placement(simulate_seeded(cluster, prefix))
        b = placement(on_host(simulate_seeded, cluster, prefix))
        check(a == b, "serial placement differs from the host CPU's: "
              + first_difference(a, b))
        say(f"[b:serial-prefix-host-reference] pods={len(a[0]) + len(a[1])} "
            "byte_equal=True")

    with phase("b:plan"):
        seed_name_hashes(7)
        plan = plan_capacity_incremental(
            cluster, apps, template, max_new_nodes=128,
            materialize=False, verify=True, precompile=True,
        )
        say(
            f"[b:plan] success={plan.success} nodes_added={plan.nodes_added} "
            f"probes={json.dumps(plan.probes, sort_keys=True)} "
            f"audit_ok={plan.audit.get('ok')}"
        )
        check(plan.success, f"plan failed: {plan.message}")
        check(plan.audit.get("ok") is True, f"plan not certified: {plan.audit}")


def serve() -> None:
    import tempfile

    from simtpu.api import simulate
    from simtpu.durable.checkpoint import name_seed
    from simtpu.serve import ServeOptions, SimtpuServer
    from simtpu.serve.batching import app_from_payload
    from simtpu.workloads.expand import seed_name_hashes
    import simtpu.constants as C

    def request(port, method, path, body=None):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
        try:
            conn.request(method, path, json.dumps(body) if body else None,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    with tempfile.TemporaryDirectory(prefix="simtpu-smoke-") as state_dir, \
            phase("c:serve"):
        server = SimtpuServer(ServeOptions(
            port=0, state_dir=state_dir, default_deadline_s=600.0,
        ))
        port = server.start()
        try:
            status, doc = request(port, "POST", "/v1/sessions",
                                  {"config": CONFIGS[0][0]})
            check(status in (200, 201), f"session create: {status} {doc}")
            sid = doc["session"]
            status, fit = request(port, "POST", f"/v1/sessions/{sid}/fit",
                                  FIT_PAYLOAD)
            check(status == 200, f"fit: {status} {fit}")
            session = server.store.get(sid)
            node = list(session.node_index)[1]
            status, drain = request(port, "POST", f"/v1/sessions/{sid}/drain",
                                    {"nodes": [node]})
            check(status == 200, f"drain: {status} {drain}")
            status, cap = request(port, "POST", f"/v1/sessions/{sid}/capacity",
                                  {})
            check(status == 200, f"capacity: {status} {cap}")
            say(
                f"[c:serve] fit fits={fit['fits']} audit_ok={fit['audit']['ok']}"
                f" drain evicted={drain['evicted']} unplaced={drain['unplaced']}"
                f" capacity success={cap['success']} "
                f"nodes_added={cap['nodes_added']}"
            )
            check(fit["audit"]["ok"] is True, "served fit not certified")

            # the serve contract: the fit equals a one-shot run of the
            # session's snapshot plus the query, under the served seed
            with session.lock:
                seed_name_hashes(name_seed(fit["fingerprint"]))
                result = simulate(
                    session.cluster,
                    list(session.apps) + [app_from_payload(FIT_PAYLOAD)],
                    sched_config=session.sched_config,
                )

            def is_query(pod):
                labels = (pod.get("metadata") or {}).get("labels") or {}
                return labels.get(C.LABEL_APP_NAME) == fit["app"]

            oneshot = {}
            for s in result.node_status:
                names = sorted(p["metadata"]["name"] for p in s.pods if is_query(p))
                if names:
                    oneshot[s.node["metadata"]["name"]] = names
            unsched = sum(1 for u in result.unscheduled_pods if is_query(u.pod))
            check(fit["placements"] == oneshot and fit["unscheduled"] == unsched,
                  f"served fit {fit['placements']} != one-shot {oneshot}")
            say("[c:serve] fit_equals_one_shot=True")
        finally:
            server.force_stop()


def sharded() -> None:
    """The four-chip path: node-sharded bulk rounds against the single-chip
    rounds engine, and the auto-sharded incremental plan against the
    unsharded one, on phase b's problem."""
    import jax

    from simtpu.parallel import ShardedRoundsEngine, make_mesh
    from simtpu.plan.capacity import ApplierOptions, _resolve_engines
    from simtpu.plan.incremental import plan_capacity_incremental
    from simtpu.workloads.expand import seed_name_hashes

    with phase("4:build"):
        cluster, apps, template = phase_b_problem()
        mesh = make_mesh(jax.devices()[:4])
        _, _, plan_mesh = _resolve_engines(ApplierOptions(), cluster, apps)
        check(plan_mesh is not None and plan_mesh.devices.size == 4,
              f"the planner did not auto-shard over 4 chips: {plan_mesh}")

    with phase("4:sharded-bulk"):
        many = simulate_seeded(
            cluster, apps, engine_factory=lambda t: ShardedRoundsEngine(t, mesh)
        )
        report_placement("4:sharded-bulk", many)
    with phase("4:single-chip-bulk"):
        one = simulate_seeded(cluster, apps, bulk=True)
        report_placement("4:single-chip-bulk", one)
        a, b = placement(many), placement(one)
        check(a == b, "sharded placement differs from the single-chip one: "
              + first_difference(a, b))
        say("[4:single-chip-bulk] byte_equal=True")

    plans = {}
    for label, m in (("sharded", plan_mesh), ("single-chip", None)):
        with phase(f"4:plan-{label}"):
            seed_name_hashes(7)
            plan = plan_capacity_incremental(
                cluster, apps, template, max_new_nodes=128,
                materialize=False, verify=True, precompile=True, mesh=m,
            )
            say(
                f"[4:plan-{label}] success={plan.success} "
                f"nodes_added={plan.nodes_added}"
                f" probes={json.dumps(plan.probes, sort_keys=True)} "
                f"audit_ok={plan.audit.get('ok')}"
            )
            check(plan.success, f"{label} plan failed: {plan.message}")
            check(plan.audit.get("ok") is True, f"{label} plan not certified")
            plans[label] = (plan.nodes_added, plan.probes)
    check(plans["sharded"] == plans["single-chip"],
          f"sharded plan {plans['sharded']} != unsharded {plans['single-chip']}")
    say("[4:plan] answers_equal=True")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the node-sharded path on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    os.chdir(REPO)  # the example configs' inner paths are repo-relative

    from simtpu import native
    from simtpu.cache import enable_compilation_cache
    from simtpu.obs.metrics import REGISTRY

    say(f"compilation cache: {enable_compilation_cache()}")
    say(f"native host library built: {native.available()}")
    say(f"device: {devices[0].device_kind} x{len(devices)}")
    if args.chips == 4:
        sharded()
    else:
        conformance()
        real_size()
        serve()
    failures = REGISTRY.value("aot.failures")
    say(f"aot.failures={failures}")
    check(failures == 0, f"{failures} AOT precompiles failed and fell back")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
