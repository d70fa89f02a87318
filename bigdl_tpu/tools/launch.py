"""``bigdl-tpu-launch`` — one command that hides cluster topology.

The reference wraps its whole Spark topology behind single launcher
scripts (ref: scripts/spark-submit-with-bigdl.sh:1,
pyspark-with-bigdl.sh:1); this is the TPU-pod analog (SURVEY §7 "Hard
parts"): it wires ``jax.distributed.initialize`` coordinator/rank and
then execs the user's training main, so user code never touches
topology.

Three ways in:

* **TPU pod slice** (default, no flags)::

      gcloud compute tpus tpu-vm ssh $TPU --worker=all \\
          --command "bigdl-tpu-launch train.py --epochs 10"

  Every host runs the same line; ``jax.distributed.initialize()``
  auto-discovers coordinator/rank/process-count from the TPU metadata.
  On a single non-pod host the auto-init is skipped and the script just
  runs (so the same command works from a laptop to a v5e-256).

* **Explicit cluster** (non-TPU or custom DNS)::

      bigdl-tpu-launch --coordinator host0:1234 --num-procs 4 \\
          --proc-id $RANK train.py

* **Local multi-process grid** (``--procs N``) — the testing mode: N
  processes on THIS host form a real ``jax.distributed`` cluster on the
  CPU backend, each with ``--cpu-devices K`` virtual devices (an
  N×K-device pod without hardware; the validated recipe of
  tests/multihost_child.py)::

      bigdl-tpu-launch --procs 2 --cpu-devices 4 train.py
"""

from __future__ import annotations

import argparse
import os
import runpy
import socket
import subprocess
import sys

_ENV_COORD = "BIGDL_TPU_COORDINATOR"
_ENV_NPROCS = "BIGDL_TPU_NUM_PROCS"
_ENV_PID = "BIGDL_TPU_PROC_ID"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_user_main(script: str, script_args, as_module: bool) -> None:
    """Exec the user's main in THIS process (distributed is already up),
    exactly as ``python script.py args`` / ``python -m pkg.mod args``
    would see it."""
    sys.argv = [script] + list(script_args)
    if as_module:
        runpy.run_module(script, run_name="__main__", alter_sys=True)
    else:
        runpy.run_path(script, run_name="__main__")


# Child bootstrap for the local grid, run via `python -c` so NOTHING
# (not even this package, whose import touches jax) loads before
# jax.distributed.initialize — the ordering jax requires. A FAILING rank
# must os._exit: the normal exit path runs jax's atexit distributed
# shutdown, which is a BARRIER over all ranks — a crashed rank would
# block there forever waiting for peers that are stuck waiting for it.
# Successful ranks exit normally (all reach the barrier; it completes).
_BOOTSTRAP = f"""
import os, runpy, sys, traceback
import jax
jax.distributed.initialize(os.environ['{_ENV_COORD}'],
                           num_processes=int(os.environ['{_ENV_NPROCS}']),
                           process_id=int(os.environ['{_ENV_PID}']))
tgt = sys.argv[1]
as_mod = sys.argv[2] == '1'
sys.argv = [tgt] + sys.argv[3:]
try:
    if as_mod:
        runpy.run_module(tgt, run_name='__main__', alter_sys=True)
    else:
        runpy.run_path(tgt, run_name='__main__')
except SystemExit as e:
    code = e.code if isinstance(e.code, int) else (0 if e.code is None else 1)
    if code:
        sys.stderr.flush(); sys.stdout.flush()
        os._exit(code)
except BaseException:
    traceback.print_exc()
    sys.stderr.flush(); sys.stdout.flush()
    os._exit(1)
"""


def _spawn_local_grid(args) -> int:
    port = args.port or _free_port()
    env_base = dict(os.environ)
    # CPU backend for the virtual grid: the children never ask for a chip
    env_base["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env_base.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(
        f"--xla_force_host_platform_device_count={args.cpu_devices}")
    env_base["XLA_FLAGS"] = " ".join(flags)

    procs = []
    for i in range(args.procs):
        env = dict(env_base)
        env[_ENV_COORD] = f"localhost:{port}"
        env[_ENV_NPROCS] = str(args.procs)
        env[_ENV_PID] = str(i)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _BOOTSTRAP, args.script,
             "1" if args.module else "0", *args.script_args], env=env))
    # poll rather than wait sequentially: a crashed rank strands its
    # peers inside collectives, so the FIRST failure must kill survivors
    # or the launcher would hang on them forever
    import time as _time

    rc = 0
    live = list(procs)
    while live:
        for p in list(live):
            code = p.poll()
            if code is None:
                continue
            live.remove(p)
            if code and not rc:
                rc = code
                for q in live:
                    q.kill()
        if live:
            _time.sleep(0.2)
    return rc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="bigdl-tpu-launch",
        description="Launch a training main with jax.distributed wired up "
                    "(TPU-pod auto-discovery, explicit cluster, or a local "
                    "N-process CPU grid for testing)")
    p.add_argument("--procs", type=int, default=None,
                   help="local grid: spawn N processes on this host")
    p.add_argument("--cpu-devices", type=int, default=1,
                   help="local grid: virtual CPU devices per process")
    p.add_argument("--port", type=int, default=None,
                   help="local grid: coordinator port (default: free port)")
    p.add_argument("--coordinator", default=None,
                   help="explicit cluster: coordinator host:port")
    p.add_argument("--num-procs", type=int, default=None,
                   help="explicit cluster: total process count")
    p.add_argument("--proc-id", type=int, default=None,
                   help="explicit cluster: this process's rank")
    p.add_argument("-m", "--module", action="store_true",
                   help="treat the target as a module name (python -m style)")
    p.add_argument("script", help="training script (or module with -m) to run")
    p.add_argument("script_args", nargs=argparse.REMAINDER,
                   help="arguments passed through to the script")
    args = p.parse_args(argv)
    if args.procs is not None:
        if args.procs < 1:
            p.error("--procs must be >= 1")
        return _spawn_local_grid(args)

    import jax

    if args.coordinator is not None:
        if args.num_procs is None or args.proc_id is None:
            p.error("--coordinator needs --num-procs and --proc-id")
        jax.distributed.initialize(args.coordinator,
                                   num_processes=args.num_procs,
                                   process_id=args.proc_id)
    else:
        try:
            # TPU pod: coordinator/rank auto-discovered from metadata
            jax.distributed.initialize()
        except Exception as e:  # single host / no cluster env — run anyway
            print(f"bigdl-tpu-launch: single-process run "
                  f"(auto-init skipped: {e})", file=sys.stderr)
    _run_user_main(args.script, args.script_args, args.module)
    return 0


if __name__ == "__main__":
    sys.exit(main())
