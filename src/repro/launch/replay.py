"""Trace-driven metal deployment launcher (the sim-to-metal harness CLI).

Loads a recorded ``SimTrace`` (``launch/sim.py --record``), rebuilds the
recorded scenario from its header provenance, and executes the schedule on
live devices through ``repro.sim.metal.MetalReplay``:

  * default: single process, chains sharded over this host's devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives a real
    multi-device mesh on CPU — the CI fallback);
  * ``--processes N``: self-spawns N local processes, each joining a
    ``jax.distributed`` coordinator (``launch/mesh.py make_metal_mesh``)
    and computing a contiguous chain slice; trajectories merge through a
    length-prefixed TCP all-gather (:class:`SocketExchange`, hub at rank
    0). Every process runs the identical replicated finalize, and the
    final device matrices are digest-compared across ranks.

``--check`` replays the trace through the virtual-time simulator in-process
and holds the metal state to it: bit-exact at fp32, within the sim's own
different-key quantization spread (x ``--tolerance-factor``) at bits<32.
``--fault-inject`` re-derives the executed-step masks from the recorded
churn/straggler timeline instead of trusting them (``--stall-scale`` turns
the deficit into real process stalls). ``--obs`` writes a metal-side
telemetry stream diffable against the sim's:
``python tools/obs_diff.py sim_obs.jsonl metal_obs.jsonl``.

Examples:
  PYTHONPATH=src python -m repro.launch.sim --scenario uniform_sync \\
      --record trace.jsonl
  PYTHONPATH=src python -m repro.launch.replay --trace trace.jsonl --check
  PYTHONPATH=src python -m repro.launch.replay --trace trace.jsonl \\
      --processes 2 --check --obs metal_obs.jsonl
  PYTHONPATH=src python -m repro.launch.replay --trace trace.jsonl \\
      --fault-inject --stall-scale 0.01
"""
from __future__ import annotations

import argparse
import hashlib
import os
import pickle
import socket
import struct
import subprocess
import sys
import time


def _send_msg(sock: socket.socket, blob: bytes) -> None:
    sock.sendall(struct.pack("!Q", len(blob)) + blob)


def _recv_msg(sock: socket.socket) -> bytes:
    buf = b""
    while len(buf) < 8:
        chunk = sock.recv(8 - len(buf))
        if not chunk:
            raise ConnectionError("exchange peer closed mid-header")
        buf += chunk
    (n,) = struct.unpack("!Q", buf)
    out = bytearray()
    while len(out) < n:
        chunk = sock.recv(min(1 << 20, n - len(out)))
        if not chunk:
            raise ConnectionError("exchange peer closed mid-payload")
        out += chunk
    return bytes(out)


class SocketExchange:
    """All-gather over localhost TCP: rank 0 is the hub — it collects every
    shard's payload, assembles the rank-ordered list, and broadcasts it
    back. Payloads are pickled numpy arrays with an 8-byte length prefix.
    This is the deployment's *message plane*, deliberately separate from
    XLA: a DFedRW fleet exchanges models over a network (see
    ``repro.sim.metal``)."""

    def __init__(self, n_shards: int, shard_id: int, host: str, port: int,
                 timeout_s: float = 120.0):
        self.n_shards = int(n_shards)
        self.shard_id = int(shard_id)
        self._conns: dict[int, socket.socket] = {}
        self._sock = None
        self._srv = None
        if self.shard_id == 0:
            self._srv = socket.create_server((host, port))
            self._srv.settimeout(timeout_s)
            for _ in range(self.n_shards - 1):
                conn, _ = self._srv.accept()
                conn.settimeout(timeout_s)
                (rank,) = struct.unpack("!Q", _recv_msg(conn))
                self._conns[rank] = conn
        else:
            deadline = time.monotonic() + timeout_s
            while True:
                try:
                    self._sock = socket.create_connection(
                        (host, port), timeout=timeout_s)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)
            self._sock.settimeout(timeout_s)
            _send_msg(self._sock, struct.pack("!Q", self.shard_id))

    def allgather(self, payload) -> list:
        if self.shard_id == 0:
            received = {0: payload}
            for rank, conn in self._conns.items():
                received[rank] = pickle.loads(_recv_msg(conn))
            out = [received[r] for r in range(self.n_shards)]
            blob = pickle.dumps(out, protocol=pickle.HIGHEST_PROTOCOL)
            for conn in self._conns.values():
                _send_msg(conn, blob)
            return out
        _send_msg(self._sock,
                  pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL))
        return pickle.loads(_recv_msg(self._sock))

    def close(self) -> None:
        for conn in self._conns.values():
            conn.close()
        if self._srv is not None:
            self._srv.close()
        if self._sock is not None:
            self._sock.close()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_workers(args: argparse.Namespace) -> int:
    """Parent path of ``--processes N``: pick coordinator/exchange ports,
    spawn N worker copies of this CLI (rank 0 carries --check/--obs), and
    fail if any worker fails. The workers run on the CPU backend: this is a
    conformance harness, and on an accelerator host every worker would
    otherwise try to open every local chip."""
    coord_port, exch_port = _free_port(), _free_port()
    procs = []
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print(f"replay: {args.processes} worker processes on the CPU backend",
          flush=True)
    if args.host_devices:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")
    for rank in range(args.processes):
        cmd = [sys.executable, "-m", "repro.launch.replay",
               "--trace", args.trace,
               "--processes", str(args.processes),
               "--process-id", str(rank),
               "--coordinator", f"127.0.0.1:{coord_port}",
               "--exchange-port", str(exch_port),
               "--eval-every", str(args.eval_every),
               "--tolerance-factor", str(args.tolerance_factor),
               "--stall-scale", str(args.stall_scale)]
        if args.fault_inject:
            cmd.append("--fault-inject")
        if rank == 0:
            if args.check:
                cmd.append("--check")
            if args.obs:
                cmd += ["--obs", args.obs]
        procs.append(subprocess.Popen(cmd, env=env))
    rc = 0
    for rank, p in enumerate(procs):
        code = p.wait()
        if code != 0:
            print(f"worker {rank} exited with {code}", file=sys.stderr)
            rc = 1
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--trace", required=True,
                    help="recorded SimTrace JSONL (launch/sim.py --record)")
    ap.add_argument("--processes", type=int, default=1,
                    help="localhost deployment size; >1 self-spawns workers")
    ap.add_argument("--process-id", type=int, default=-1,
                    help="internal: this worker's rank (set by the parent)")
    ap.add_argument("--coordinator", default="",
                    help="jax.distributed coordinator host:port (workers)")
    ap.add_argument("--exchange-port", type=int, default=0,
                    help="internal: trajectory-exchange hub port (workers)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force this many virtual host devices per worker "
                         "(sets XLA_FLAGS for spawned processes)")
    ap.add_argument("--check", action="store_true",
                    help="replay through the simulator in-process and hold "
                         "the metal trajectory to it (bit-exact at fp32, "
                         "quantization tolerance below 32 bits)")
    ap.add_argument("--tolerance-factor", type=float, default=4.0,
                    help="bits<32 tolerance: allowed metal deviation as a "
                         "multiple of the sim's own different-key replay "
                         "spread")
    ap.add_argument("--fault-inject", action="store_true",
                    help="re-derive exec masks / dead aggregators from the "
                         "recorded churn+straggler timeline and verify the "
                         "live degradation matches the sim's")
    ap.add_argument("--stall-scale", type=float, default=0.0,
                    help="with --fault-inject: real seconds slept per "
                         "recorded missing step (0 = derive only)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="eval cadence (0 = the trace header's)")
    ap.add_argument("--obs", default="",
                    help="write the metal-side repro.obs stream here (diff "
                         "vs the sim stream with tools/obs_diff.py)")
    args = ap.parse_args(argv)

    if args.processes > 1 and args.process_id < 0:
        return _spawn_workers(args)

    import jax
    import numpy as np

    from repro.core.dfedrw import DFedRW
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_metal_mesh
    from repro.sim import FaultInjector, MetalReplay, SimTrace, \
        build_scenario, conformance_diff

    enable_compile_cache()
    trace = SimTrace.load(args.trace)
    h = trace.header
    if not {"scenario", "build_seed", "key_seed"} <= set(h):
        raise SystemExit(
            "trace header lacks launcher provenance (scenario/build_seed/"
            "key_seed): record it via `python -m repro.launch.sim --record`")
    setup = build_scenario(h["scenario"], n=h["n"], seed=h["build_seed"],
                           **dict(h.get("build_overrides", {})))

    rank = max(args.process_id, 0)
    # this worker's chain slice sizes its local mesh (contiguous split,
    # same arithmetic as MetalReplay._shard_slice)
    m_local = len(np.array_split(np.arange(h["m_chains"]),
                                 max(args.processes, 1))[rank])
    mesh, info = make_metal_mesh(
        chains=m_local,
        coordinator=args.coordinator or None,
        num_processes=args.processes if args.processes > 1 else 1,
        process_id=rank)
    if args.processes > 1:
        exchange = SocketExchange(args.processes, rank, "127.0.0.1",
                                  args.exchange_port)
    else:
        exchange = None
    print(f"metal[{rank}]: trace={args.trace} scenario={h['scenario']} "
          f"n={h['n']} windows={len(trace.windows)} bits={h['bits']} "
          f"processes={info['process_count']} "
          f"devices local={info['local_devices']} "
          f"global={info['global_devices']} mesh_axis={info['mesh_axis']}")

    engine = DFedRW(setup.model, setup.data, setup.topo, setup.cfg)
    metal = MetalReplay(engine, exchange=exchange,
                        devices=list(mesh.devices.ravel()))
    rec = None
    if args.obs:
        from repro.obs import Recorder, VirtualClock
        rec = Recorder(clock=VirtualClock())
        metal.attach_obs(rec)
    fault = (FaultInjector(policy=h["policy"],
                           stall_scale=args.stall_scale)
             if args.fault_inject else None)
    eval_every = args.eval_every or max(h.get("eval_every", 1), 1)
    key = jax.random.PRNGKey(h["key_seed"])
    result = metal.run(trace, key, setup.x_test, setup.y_test,
                       eval_every=eval_every, fault=fault)
    final = result.history.final()
    print(f"metal[{rank}]: done acc={final['accuracy']:.4f} "
          f"best={final['best_accuracy']:.4f} "
          f"virtual_time={result.virtual_time_s:.1f}s")
    if fault is not None:
        print(f"metal[{rank}]: faults verified — stalls={fault.stalls_injected} "
              f"steps_stalled={fault.steps_stalled} "
              f"aggregators_dropped={fault.aggregators_dropped}")

    digest = hashlib.sha256(
        np.ascontiguousarray(result.device_matrix).tobytes()).hexdigest()
    if exchange is not None:
        digests = exchange.allgather(digest)
        if len(set(digests)) != 1:
            print(f"metal[{rank}]: SHARD DIVERGENCE {digests}",
                  file=sys.stderr)
            return 1
        print(f"metal[{rank}]: shards agree digest={digest[:16]}")
        exchange.close()

    rc = 0
    if args.check:
        sim_res = setup.runner().replay(
            trace, jax.random.PRNGKey(h["key_seed"]),
            setup.x_test, setup.y_test, eval_every=eval_every)
        diff = conformance_diff(sim_res, result)
        quantized = any(
            (w.bits if w.bits is not None else h["bits"]) < 32
            for w in trace.windows)
        if not quantized:
            tol, basis = 0.0, "bit-exact (fp32)"
        else:
            alt = setup.runner().replay(
                trace, jax.random.PRNGKey(h["key_seed"] + 104729),
                setup.x_test, setup.y_test, eval_every=eval_every)
            spread = conformance_diff(sim_res, alt)
            tol = args.tolerance_factor * spread
            basis = (f"{args.tolerance_factor}x different-key sim spread "
                     f"{spread:.3e}")
        ok = diff <= tol
        print(f"conformance: max_abs_diff={diff:.3e} tolerance={tol:.3e} "
              f"({basis}) -> {'OK' if ok else 'FAIL'}")
        rc = 0 if ok else 1
    if rec is not None:
        from repro.obs import provenance
        rec.save(args.obs, provenance=provenance(config=vars(args)),
                 workload="metal", scenario=h["scenario"])
        print(f"obs: wrote {args.obs} (diff vs sim: "
              f"python tools/obs_diff.py <sim_obs> {args.obs})")
    return rc


if __name__ == "__main__":
    sys.exit(main())
