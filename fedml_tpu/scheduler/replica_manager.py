"""Process-level model-serving replicas with autoscaling and self-healing.

Capability parity: reference `model_scheduler/device_model_deployment.py:
89-928` brings endpoints up as separate containers, the job monitor
(`comm_utils/job_monitor.py:63-699`) restarts dead replicas, and the
autoscale/reset logic resizes them.  TPU-era, container-free equivalent:
each replica is an OS PROCESS serving a model card over HTTP
(`replica_worker.py` → FedMLInferenceRunner); this manager

* spawns/retires replicas (``scale_to`` — the `ReplicaAutoscaler`'s
  apply_fn),
* health-checks and RESTARTS crashed replicas (monitor thread),
* round-robins requests across live replicas (the inference-gateway role
  of `device_model_inference.py`).
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional

from ..core.mlops.lock_profiler import named_rlock


class _Replica:
    def __init__(self, proc: subprocess.Popen, port: int) -> None:
        self.proc = proc
        self.port = port
        self.restarts = 0


class ReplicaProcessManager:
    def __init__(self, card_name: str, registry_root: Optional[str] = None,
                 host: str = "127.0.0.1", base_port: int = 0,
                 ready_timeout_s: float = 60.0,
                 monitor_interval_s: float = 0.5) -> None:
        self.card_name = card_name
        self.registry_root = registry_root
        self.host = host
        # base_port 0 → pick a free ephemeral base once, then offset per slot
        self.base_port = base_port or self._free_port()
        self.ready_timeout_s = float(ready_timeout_s)
        self.monitor_interval_s = float(monitor_interval_s)
        self.replicas: List[Optional[_Replica]] = []
        self._rr = 0
        self._lock = named_rlock("ReplicaProcessManager._lock")       # replica-list access (fast)
        self._scale_lock = named_rlock("ReplicaProcessManager._scale_lock")  # lifecycle ops (slow)
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None

    @staticmethod
    def _free_port() -> int:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    # -- lifecycle ----------------------------------------------------------
    def _spawn(self, slot: int) -> _Replica:
        port = self.base_port + slot
        cmd = [sys.executable, "-m",
               "fedml_tpu.scheduler.replica_worker",
               "--card", self.card_name, "--host", self.host,
               "--port", str(port)]
        if self.registry_root:
            cmd += ["--root", self.registry_root]
        env = dict(os.environ)
        # a chip belongs to one process at a time, and this parent may
        # hold it: replica workers stay on the CPU unless the operator
        # hands them the chip by setting JAX_PLATFORMS for them
        env.setdefault("JAX_PLATFORMS", "cpu")
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                                stderr=subprocess.STDOUT)
        rep = _Replica(proc, port)
        self._wait_ready(rep)
        logging.info("replica[%d] pid=%d serving on :%d", slot, proc.pid,
                     port)
        return rep

    def _wait_ready(self, rep: _Replica) -> None:
        deadline = time.time() + self.ready_timeout_s
        while time.time() < deadline and not self._stop.is_set():
            if rep.proc.poll() is not None:
                raise RuntimeError(
                    f"replica on :{rep.port} exited rc={rep.proc.returncode}"
                    " before becoming ready")
            try:
                with urllib.request.urlopen(
                        f"http://{self.host}:{rep.port}/ready",
                        timeout=2) as r:
                    if json.loads(r.read()).get("ready"):
                        return
            except Exception:  # noqa: BLE001 — still booting
                time.sleep(0.1)
        # kill the half-booted child: leaving it running would squat the
        # slot's port and leak a process (shutdown mid-boot lands here too,
        # so a closing manager never waits out the full ready timeout)
        self._kill(rep)
        raise TimeoutError(
            f"replica on :{rep.port} never became ready"
            + (" (shutdown requested)" if self._stop.is_set() else ""))

    def scale_to(self, n: int) -> int:
        """Grow/shrink to n replicas (the autoscaler's apply_fn).  Spawning
        (slow: process boot + ready poll) happens OUTSIDE the gateway lock
        so predict() keeps serving from live replicas meanwhile; the
        scale lock serializes concurrent resizes."""
        n = max(int(n), 0)
        with self._scale_lock:
            while self.live_count() < n:
                with self._lock:
                    slot = self._first_free_slot()
                    if slot == len(self.replicas):
                        self.replicas.append(None)  # reserve
                rep = self._spawn(slot)
                with self._lock:
                    self.replicas[slot] = rep
            victims = []
            with self._lock:
                while self.live_count() > n:
                    slot = max(i for i, r in enumerate(self.replicas)
                               if r is not None)
                    victims.append(self.replicas[slot])
                    self.replicas[slot] = None
            for victim in victims:
                self._kill(victim)
        return self.live_count()

    def _first_free_slot(self) -> int:
        # _lock is an RLock: scale_to calls this with it already held,
        # and taking it here keeps the scan safe for any future caller
        with self._lock:
            for i, r in enumerate(self.replicas):
                if r is None:
                    return i
            return len(self.replicas)

    @staticmethod
    def _kill(rep: _Replica) -> None:
        if rep.proc.poll() is None:
            rep.proc.terminate()
            try:
                rep.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                rep.proc.kill()

    def live_count(self) -> int:
        # snapshot under the gateway lock: the monitor and scale threads
        # mutate the slot list concurrently
        with self._lock:
            return sum(1 for r in self.replicas
                       if r is not None and r.proc.poll() is None)

    def rolling_restart(self) -> None:
        """Restart replicas ONE AT A TIME (version rollout/rollback: each
        respawn loads the card's now-current version; the other slots keep
        serving).  The slot is retired (None) around the swap so the
        monitor can't double-spawn it."""
        with self._scale_lock:
            for slot in range(len(self.replicas)):
                with self._lock:
                    rep = self.replicas[slot]
                    if rep is None:
                        continue
                    self.replicas[slot] = None      # retire during swap
                self._kill(rep)
                try:
                    new = self._spawn(slot)
                except Exception:
                    # reinstall the (dead) old replica: the monitor loop
                    # retries DEAD slots every tick, so capacity heals
                    # once the card becomes loadable again — a None slot
                    # would be lost forever
                    with self._lock:
                        self.replicas[slot] = rep
                    raise
                new.restarts = rep.restarts + 1
                with self._lock:
                    self.replicas[slot] = new

    # -- self-healing monitor ----------------------------------------------
    def start_monitor(self) -> None:
        if self._monitor is None:
            self._monitor = threading.Thread(target=self._monitor_loop,
                                             daemon=True,
                                             name="replica-monitor")
            self._monitor.start()

    def _monitor_loop(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                dead = [(slot, rep) for slot, rep in
                        enumerate(self.replicas)
                        if rep is not None and rep.proc.poll() is not None]
            for slot, rep in dead:
                logging.warning("replica[%d] died rc=%s — restarting",
                                slot, rep.proc.returncode)
                try:
                    # spawn outside the gateway lock: live replicas keep
                    # serving during the restart window
                    new = self._spawn(slot)
                except Exception:  # noqa: BLE001
                    # a failed restart (port stolen, card unloadable) must
                    # not kill the monitor — log and retry next tick
                    logging.exception("replica[%d] restart failed; will "
                                      "retry", slot)
                    continue
                new.restarts = rep.restarts + 1
                with self._lock:
                    # a concurrent scale_to shrink may have retired this
                    # slot (set it None) or replaced it while we were
                    # spawning; installing unconditionally would resurrect
                    # the slot and exceed the requested replica count
                    installed = (slot < len(self.replicas)
                                 and self.replicas[slot] is rep)
                    if installed:
                        self.replicas[slot] = new
                if not installed:
                    logging.info("replica[%d] retired during restart — "
                                 "discarding replacement", slot)
                    self._kill(new)
            self._stop.wait(self.monitor_interval_s)

    # -- gateway ------------------------------------------------------------
    def predict(self, payload: Dict[str, Any], timeout: float = 30.0
                ) -> Any:
        """Round-robin a request across live replicas (one retry on a
        replica that dies mid-request)."""
        for _ in range(2):
            with self._lock:
                live = [r for r in self.replicas
                        if r is not None and r.proc.poll() is None]
                if not live:
                    raise RuntimeError("no live replicas")
                rep = live[self._rr % len(live)]
                self._rr += 1
            try:
                req = urllib.request.Request(
                    f"http://{self.host}:{rep.port}/predict",
                    data=json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return json.loads(r.read())
            except Exception:  # noqa: BLE001 — retry once on another replica
                continue
        raise RuntimeError("predict failed on all tried replicas")

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"live": self.live_count(),
                    "slots": len(self.replicas),
                    "restarts": sum(r.restarts for r in self.replicas
                                    if r is not None)}

    def shutdown(self) -> None:
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5)
            if self._monitor.is_alive():
                logging.warning("replica monitor did not stop within 5s "
                                "(mid-spawn); it will exit on its next "
                                "tick")
            self._monitor = None
        # serialize with any in-flight scale_to/rolling_restart: their
        # _wait_ready aborts promptly on _stop, and killing/clearing the
        # slots under them would leak the replica they are about to
        # install
        with self._scale_lock:
            with self._lock:
                for rep in self.replicas:
                    if rep is not None:
                        self._kill(rep)
                self.replicas = []
