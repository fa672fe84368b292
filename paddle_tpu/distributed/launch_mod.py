"""Launcher.

Reference parity: python/paddle/distributed/fleet/launch.py:396 (process
launcher setting PADDLE_TRAINER_ID/ENDPOINTS per proc) and
python/paddle/distributed/spawn.py.

TPU-native: one controller process normally drives all local chips, so
`spawn(fn)` simply runs fn — per-DEVICE processes are not a thing here.
Multi-CONTROLLER runs are: `--nproc_per_node N` spawns N processes that
jax.distributed.initialize against a coordinator (loopback by default;
combine with --coordinator/--nnodes/--node_rank for multi-host), each
seeing the global device set. `--server_num/--worker_num` spawns a local
parameter-server cluster instead.
"""
import os
import runpy
import sys


def spawn(func, args=(), nprocs=1, join=True, daemon=False, **options):
    if nprocs not in (1, -1):
        raise RuntimeError(
            "paddle_tpu uses single-controller SPMD: one process drives "
            "all chips. Express device parallelism with fleet "
            "hybrid_configs / Mesh, or launch a multi-controller run "
            "with `python -m paddle_tpu.distributed.launch_mod "
            "--nproc_per_node N script.py`.")
    return func(*args)


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_ps_cluster(server_num, worker_num, script, script_args):
    """Reference: fleet/launch.py PS mode — spawn server processes
    (TRAINING_ROLE=PSERVER, POD_IP/PADDLE_PORT) and worker processes
    (TRAINING_ROLE=TRAINER, PADDLE_TRAINER_ID), all sharing
    PADDLE_PSERVERS_IP_PORT_LIST / PADDLE_TRAINER_ENDPOINTS."""
    import subprocess
    server_eps = [f"127.0.0.1:{_free_port()}" for _ in range(server_num)]
    worker_eps = [f"127.0.0.1:{_free_port()}" for _ in range(worker_num)]
    base = dict(os.environ)
    base["PADDLE_PSERVERS_IP_PORT_LIST"] = ",".join(server_eps)
    base["PADDLE_TRAINER_ENDPOINTS"] = ",".join(worker_eps)
    base["PADDLE_TRAINERS_NUM"] = str(worker_num)
    procs = []
    for i, ep in enumerate(server_eps):
        env = dict(base)
        ip, port = ep.rsplit(":", 1)
        env.update(TRAINING_ROLE="PSERVER", POD_IP=ip, PADDLE_PORT=port)
        procs.append(("server", subprocess.Popen(
            [sys.executable, script] + script_args, env=env)))
    for i in range(worker_num):
        env = dict(base)
        env.update(TRAINING_ROLE="TRAINER", PADDLE_TRAINER_ID=str(i))
        procs.append(("worker", subprocess.Popen(
            [sys.executable, script] + script_args, env=env)))
    # reference launcher semantics: wait for workers; servers are
    # terminated when training finishes
    rc = 0
    for kind, p in procs:
        if kind == "worker":
            rc = p.wait() or rc
    _reap([p for kind, p in procs if kind == "server"])
    return rc


def _reap(procs):
    """SIGTERM, bounded wait, then SIGKILL every still-running proc."""
    import signal
    import subprocess
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def _launch_collective(nproc, script, script_args, coordinator=None,
                       nnodes=1, node_rank=0):
    """Reference: fleet/launch.py collective mode (launch.py:396 spawns
    nproc trainers with PADDLE_TRAINER_ID/ENDPOINTS). Multi-controller
    analogue: N processes per node jax.distributed.initialize against a
    coordinator (loopback when single-node); each sees the global device
    set (tested end-to-end in tests/test_dist_multiproc.py). A crashed
    rank terminates the whole job — surviving ranks would deadlock in
    their next collective waiting for it."""
    import subprocess
    import time
    import jax
    if nproc > 1 and jax.default_backend() == "tpu":
        # the children get no per-process chip binding: each would
        # claim every local chip and all but one would fail or hang
        raise SystemExit(
            f"launch: --nproc_per_node {nproc} is not supported on a "
            f"TPU host: a chip belongs to one process. Run ONE process "
            f"and drive all local chips through the mesh "
            f"(fleet.init with hybrid_configs).")
    if coordinator is None:
        coordinator = f"127.0.0.1:{_free_port()}"
    base = dict(os.environ)
    base["PADDLE_COORDINATOR"] = coordinator
    base["PADDLE_TRAINERS_NUM"] = str(nnodes * nproc)
    procs = []
    for i in range(nproc):
        env = dict(base, PADDLE_TRAINER_ID=str(node_rank * nproc + i))
        procs.append(subprocess.Popen(
            [sys.executable, script] + script_args, env=env))
    rc = 0
    try:
        while True:
            codes = [p.poll() for p in procs]
            failed = [c for c in codes if c not in (None, 0)]
            if failed:
                rc = failed[0]
                break
            if all(c == 0 for c in codes):
                break
            time.sleep(0.2)
    finally:
        _reap(procs)
    return rc


def launch():
    """python -m paddle_tpu.distributed.launch_mod
    [--coordinator host:port] [--nnodes N] [--node_rank R]
    [--nproc_per_node N]
    [--server_num N --worker_num M]  script.py args...

    With --server_num/--worker_num, spawns a local parameter-server
    cluster (reference: fleet/launch.py PS mode). With
    --nproc_per_node N (N>1), spawns a local N-process multi-controller
    collective run over a loopback coordinator."""
    argv = sys.argv[1:]
    coordinator = None
    nnodes = 1
    node_rank = 0
    server_num = 0
    worker_num = 0
    nproc_per_node = 1
    script_idx = 0
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--coordinator":
            coordinator = argv[i + 1]
            i += 2
        elif a == "--nproc_per_node":
            nproc_per_node = int(argv[i + 1])
            i += 2
        elif a == "--nnodes":
            nnodes = int(argv[i + 1])
            i += 2
        elif a == "--node_rank":
            node_rank = int(argv[i + 1])
            i += 2
        elif a == "--server_num":
            server_num = int(argv[i + 1])
            i += 2
        elif a == "--worker_num":
            worker_num = int(argv[i + 1])
            i += 2
        else:
            script_idx = i
            break
    script = argv[script_idx]
    script_args = argv[script_idx + 1:]
    if server_num > 0 and nproc_per_node > 1:
        sys.exit("--server_num (PS mode) and --nproc_per_node "
                 "(collective mode) are mutually exclusive")
    if server_num > 0 and (nnodes > 1 or coordinator):
        sys.exit("--nnodes/--coordinator do not apply to PS mode "
                 "(--server_num)")
    if nnodes > 1 and coordinator is None:
        sys.exit("--nnodes > 1 needs --coordinator host:port (a "
                 "per-node loopback coordinator cannot form one job)")
    if server_num > 0:
        sys.exit(_launch_ps_cluster(server_num, max(worker_num, 1),
                                    script, script_args))
    if nproc_per_node > 1:
        sys.exit(_launch_collective(nproc_per_node, script, script_args,
                                    coordinator=coordinator,
                                    nnodes=nnodes, node_rank=node_rank))
    if coordinator and nnodes > 1:
        os.environ["PADDLE_COORDINATOR"] = coordinator
        os.environ["PADDLE_TRAINERS_NUM"] = str(nnodes)
        os.environ["PADDLE_TRAINER_ID"] = str(node_rank)
    sys.argv = argv[script_idx:]
    runpy.run_path(script, run_name="__main__")


if __name__ == "__main__":
    launch()
