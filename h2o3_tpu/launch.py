"""Multi-process launcher — ``java -jar h2o.jar`` / ``multiNodeUtils.sh`` equivalent.

Reference: a multi-node H2O cluster is N JVMs started with the same cloud
name (``/root/reference/multiNodeUtils.sh:21-26``); each calls
``H2O.main`` → ``waitForCloudSize``. Here:

    # one process per host, same script everywhere (multi-controller SPMD)
    python -m h2o3_tpu.launch --coordinator host0:7337 \
        --num-processes 2 --process-id $I train.py [script args...]

    # or spawn an N-process cloud on THIS host (the multiNodeUtils.sh mode;
    # CPU devices are split across the processes)
    python -m h2o3_tpu.launch --fork 2 --devices-per-process 4 train.py

Each process joins the cloud via ``jax.distributed.initialize`` (blocking
until all processes connect — the reference's ``waitForCloudSize``), installs
the spanning mesh, then executes the script. All processes must run the same
script: jitted steps are one SPMD program over the global mesh.
"""

from __future__ import annotations

import argparse
import os
import runpy
import subprocess
import sys


def _run_script(script: str, argv: list[str]) -> None:
    sys.argv = [script] + argv
    runpy.run_path(script, run_name="__main__")


def main(args=None) -> int:
    ap = argparse.ArgumentParser(prog="h2o3_tpu.launch", description=__doc__)
    ap.add_argument("--coordinator", default=None,
                    help="coordinator address host:port (process 0's host)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--fork", type=int, default=None, metavar="N",
                    help="spawn an N-process cloud on this host (test mode); "
                         "the children are CPU-only by construction "
                         "(JAX_PLATFORMS=cpu, virtual devices) — a chip "
                         "belongs to one process, so no child may need it")
    ap.add_argument("--devices-per-process", type=int, default=4,
                    help="with --fork: virtual CPU devices per process")
    ap.add_argument("--port", type=int, default=7337,
                    help="with --fork: coordinator port")
    ap.add_argument("--serve", action="store_true",
                    help="serve the REST API after clouding (instead of, or "
                         "in addition to, running a script) — the k8s pod-0 "
                         "/ driver-node mode")
    ap.add_argument("--rest-port", type=int, default=54321)
    ap.add_argument("--ldap-login", default=None, metavar="URL",
                    help="gate the REST API behind an LDAP simple bind "
                         "(ldap://host:port; reference water/H2O.java "
                         "-ldap_login)")
    ap.add_argument("--ldap-user-template", default=None, metavar="DN",
                    help="bind-DN template with one {} for the login name, "
                         "e.g. 'uid={},ou=people,dc=example,dc=org'")
    ap.add_argument("script", nargs="?", default=None)
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    ns = ap.parse_args(args)
    if not ns.serve and ns.script is None:
        ap.error("a script is required unless --serve is given")

    if ns.fork:
        procs = []
        for pid in range(ns.fork):
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            flags = " ".join(
                f for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count"))
            env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count="
                                f"{ns.devices_per_process}").strip()
            cmd = [sys.executable, "-m", "h2o3_tpu.launch",
                   "--coordinator", f"localhost:{ns.port}",
                   "--num-processes", str(ns.fork), "--process-id", str(pid)]
            if ns.serve:
                cmd += ["--serve", "--rest-port", str(ns.rest_port)]
            if ns.script is not None:
                cmd += [ns.script] + ns.script_args
            procs.append(subprocess.Popen(cmd, env=env))
        # reap in any order; one failure tears down the rest (a dead
        # coordinator would leave workers blocked in initialize forever)
        import time
        rc, pending = 0, set(procs)
        while pending:
            for p in list(pending):
                code = p.poll()
                if code is None:
                    continue
                pending.discard(p)
                rc = code or rc
                if code != 0:
                    for q in pending:
                        q.terminate()
            time.sleep(0.05)
        return rc

    if ns.coordinator is not None:
        from h2o3_tpu.parallel.distributed import init_distributed
        init_distributed(ns.coordinator, ns.num_processes, ns.process_id)
    # persistent XLA compile cache (H2O3TPU_COMPILE_CACHE=1): every
    # process in the cloud shares recompile savings across launches
    from h2o3_tpu.utils import compile_cache
    compile_cache.enable()
    if ns.serve:
        import jax
        from h2o3_tpu.api import H2OServer
        # only the controller process serves (reference: the driver node's
        # REST API); workers just participate in the SPMD cloud
        if jax.process_index() == 0:
            authenticator = None
            if ns.ldap_login:
                if not ns.ldap_user_template:
                    ap.error("--ldap-login needs --ldap-user-template")
                from h2o3_tpu.api.ldap_auth import ldap_authenticator
                authenticator = ldap_authenticator(ns.ldap_login,
                                                   ns.ldap_user_template)
            server = H2OServer(port=ns.rest_port, host="0.0.0.0",
                               authenticator=authenticator).start()
            print(f"h2o3_tpu REST serving on {server.url}", flush=True)
    if ns.script is not None:
        _run_script(ns.script, ns.script_args)
    if ns.serve:
        # keep serving after the (optional) setup script: the REST server
        # runs on a daemon thread, so returning would tear it down. Workers
        # block as cloud members; REST-driven TRAINING is single-controller
        # (multi-host training uses script mode, where every process runs
        # the same SPMD program).
        import threading
        # graftlint: ok(serve forever — blocking IS this process's job)
        threading.Event().wait()
    return 0


if __name__ == "__main__":
    sys.exit(main())
