#!/usr/bin/env python
"""Multi-process SPMD job launcher (behavioral parity: tools/launch.py +
dmlc_tracker — but redesigned for jax.distributed instead of ps-lite).

The reference spawned scheduler + server + worker processes wired over
ZMQ with launch backends local/ssh/mpi/sge/yarn (`tools/launch.py:33-70`,
dmlc_tracker).  On TPU pods there are no servers: every process is an
SPMD worker that joins a `jax.distributed` cluster (coordinator =
process 0) and the collectives ride ICI/DCN.  Backends here:

  local  fork N workers on this host — CPU dev mode only.  Every
         child would claim every chip of the host, and a chip belongs
         to one process: one host's chips are driven by ONE process
         (`context=[mx.tpu(i) for i in range(n)]` / `make_mesh`, as
         chip_smoke.py's four-chip phase does), never by `-n N` here
  ssh    one worker per host from --hostfile via `ssh host env ... cmd`
         (the reference's ssh tracker role); worker 0's host doubles as
         the coordinator
  mpi    delegate process placement to `mpirun`; ranks come from
         OMPI_COMM_WORLD_RANK/PMI_RANK at runtime

All backends share one env contract (MXT_COORDINATOR, MXT_NUM_PROC,
MXT_PROC_ID) consumed by kvstore `dist_*` init; `--dry-run` prints the
commands instead of executing (CI checks the generated plans).

    python tools/launch.py -n 4 python train.py --kv-store dist_sync
    python tools/launch.py -n 2 --launcher ssh --hostfile hosts \\
        python train.py --kv-store dist_sync
"""
import argparse
import os
import shlex
import signal
import subprocess
import sys


def _env_for(rank, n, coordinator):
    return {"MXT_COORDINATOR": coordinator, "MXT_NUM_PROC": str(n),
            "MXT_PROC_ID": str(rank),
            # reference-compatible aliases (fit.py logs kvstore rank)
            "DMLC_ROLE": "worker", "DMLC_NUM_WORKER": str(n)}


def launch_local(args):
    procs = []
    try:
        for rank in range(args.num_workers):
            env = dict(os.environ)
            env.update(_env_for(rank, args.num_workers, args.coordinator))
            if args.dry_run:
                print("local[%d]: %s" % (rank, " ".join(args.command)))
                continue
            procs.append(subprocess.Popen(args.command, env=env))
        code = 0
        for proc in procs:
            proc.wait()
            code = code or proc.returncode
        return code
    except KeyboardInterrupt:
        for proc in procs:
            proc.send_signal(signal.SIGINT)
        for proc in procs:
            proc.wait()
        raise


def ssh_commands(args, hosts):
    """One worker per host; rank 0's host is the coordinator."""
    n = args.num_workers
    if len(hosts) < n:
        raise SystemExit("hostfile has %d hosts < -n %d" % (len(hosts), n))
    coord = args.coordinator
    if coord.startswith("127.") or coord.startswith("localhost"):
        # default: coordinator on worker-0's host, keep the port
        port = coord.rsplit(":", 1)[1] if ":" in coord else "8431"
        coord = "%s:%s" % (hosts[0], port)
    cmds = []
    for rank in range(n):
        envs = " ".join("%s=%s" % (k, shlex.quote(v))
                        for k, v in _env_for(rank, n, coord).items())
        inner = "cd %s && %s %s" % (
            shlex.quote(args.remote_cwd or os.getcwd()), envs,
            " ".join(shlex.quote(c) for c in args.command))
        cmds.append(["ssh", "-o", "StrictHostKeyChecking=no",
                     hosts[rank], inner])
    return cmds


def launch_ssh(args):
    with open(args.hostfile) as f:
        hosts = [h for h in (line.strip() for line in f)
                 if h and not h.startswith("#")]
    cmds = ssh_commands(args, hosts)
    if args.dry_run:
        for c in cmds:
            print("ssh: %s" % " ".join(c))
        return 0
    procs = [subprocess.Popen(c) for c in cmds]
    code = 0
    for p in procs:
        p.wait()
        code = code or p.returncode
    return code


def mpi_command(args):
    """mpirun places ranks; the trainee reads its rank from the MPI env
    (kvstore dist init falls back to OMPI_COMM_WORLD_RANK/PMI_RANK when
    MXT_PROC_ID is absent).  Env rides a portable `env K=V` prefix on
    the launched command — Open MPI's `-x` flag doesn't exist on
    MPICH/Hydra mpirun."""
    envs = ["%s=%s" % (k, v)
            for k, v in _env_for(0, args.num_workers,
                                 args.coordinator).items()
            if k != "MXT_PROC_ID"]  # per-rank, from the MPI env
    return (["mpirun", "-np", str(args.num_workers), "env"] + envs +
            args.command)


def launch_mpi(args):
    coord_host = args.coordinator.rsplit(":", 1)[0]
    if args.num_workers > 1 and coord_host in ("127.0.0.1", "localhost"):
        print("WARNING: --coordinator is loopback; multi-NODE mpi ranks "
              "cannot reach it — pass --coordinator <rank0-host>:<port> "
              "for multi-node runs", file=sys.stderr)
    cmd = mpi_command(args)
    if args.dry_run:
        print("mpi: %s" % " ".join(cmd))
        return 0
    return subprocess.call(cmd)


def main():
    p = argparse.ArgumentParser(description="launch an SPMD training job")
    p.add_argument("-n", "--num-workers", type=int, required=True,
                   help="number of worker processes")
    p.add_argument("--launcher", type=str, default="local",
                   choices=["local", "ssh", "mpi"],
                   help="local = fork on this host; ssh = one worker "
                        "per --hostfile host; mpi = delegate to mpirun")
    p.add_argument("--hostfile", type=str, default=None,
                   help="hosts file for --launcher ssh (one per line)")
    p.add_argument("--remote-cwd", type=str, default=None,
                   help="working directory on remote hosts (ssh)")
    p.add_argument("--coordinator", type=str, default="127.0.0.1:8431",
                   help="jax.distributed coordinator address")
    p.add_argument("--dry-run", action="store_true",
                   help="print the launch plan instead of executing")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="the command to launch")
    args = p.parse_args()
    if not args.command:
        p.error("no command given")
    if args.launcher == "ssh" and not args.hostfile:
        p.error("--launcher ssh requires --hostfile")

    code = {"local": launch_local, "ssh": launch_ssh,
            "mpi": launch_mpi}[args.launcher](args)
    sys.exit(code)


if __name__ == "__main__":
    main()
