"""Agent ops surface: remote-exec command registry, debug queue taps,
restart-based upgrade, and the L7 parser plugin loader.

Reference analogs: message/agent.proto:18 RemoteExecRequest (a REGISTRY of
predefined commands, never arbitrary shell), agent.proto:9 UpgradeRequest
(binary swap + restart; here re-exec picks up updated code from disk —
K8s rollouts replace the pod the same way), debug/debugger.rs:111 (queue
taps sampling live queues), plugin/wasm/mod.rs:17 (custom protocol hooks;
here plugins are python modules exporting PARSERS).
"""

from __future__ import annotations

import importlib
import json
import logging
import os
import sys
import threading

log = logging.getLogger("df.ops")

MAX_OUTPUT = 64 * 1024


class CommandRegistry:
    """Named introspection commands; nothing here shells out."""

    def __init__(self, agent) -> None:
        self.agent = agent
        self._commands = {
            "help": self._help,
            "status": self._status,
            "config": self._config,
            "queues": self._queues,
            "queue-tap": self._queue_tap,
            "flows": self._flows,
            "profilers": self._profilers,
            "spans": self._spans,
            "upgrade": self._upgrade,
            "pcap-capture": self._pcap_capture,
        }

    def names(self) -> list[str]:
        return sorted(self._commands)

    def run(self, cmd: str, args: list[str]) -> tuple[int, str]:
        fn = self._commands.get(cmd)
        if fn is None:
            return 127, f"unknown command {cmd!r}; try: " + \
                ", ".join(self.names())
        try:
            out = fn(args)
        except Exception as e:
            return 1, f"{type(e).__name__}: {e}"
        if isinstance(out, (dict, list)):
            out = json.dumps(out, default=str, sort_keys=True)
        return 0, str(out)[:MAX_OUTPUT]

    # -- commands --------------------------------------------------------------

    def _help(self, args):
        return {"commands": self.names()}

    def _status(self, args):
        a = self.agent
        return {
            "components": list(a._components),
            "pid": os.getpid(),
            "degraded": bool(a.guard is not None and a.guard.degraded),
            "sender": dict(a.sender.stats),
        }

    def _config(self, args):
        from dataclasses import asdict
        return asdict(self.agent.config)

    def _queues(self, args):
        """Queue depths across the agent (debugger.rs queue list analog)."""
        a = self.agent
        out = {"sender_queue": a.sender.queue_depth()}
        if a.dispatcher is not None:
            out["l4_buffer"] = len(a.dispatcher._l4_buf)
            out["l7_buffer"] = len(a.dispatcher._l7_buf)
        return out

    def _queue_tap(self, args):
        """Sample up to N live entries from a queue without consuming them
        (debugger.rs:111 queue tap)."""
        n = int(args[0]) if args else 8
        which = args[1] if len(args) > 1 else "sender"
        a = self.agent
        if which == "sender":
            return {"queue": "sender",
                    "entries": a.sender.peek(n)}
        if which == "l7" and a.dispatcher is not None:
            return {"queue": "l7",
                    "entries": [str(x)[:200]
                                for x in a.dispatcher._l7_buf[:n]]}
        return {"error": f"no such queue {which!r}"}

    def _flows(self, args):
        a = self.agent
        if a.dispatcher is None:
            return {"error": "flow pipeline not running"}
        return dict(a.dispatcher.stats)

    def _profilers(self, args):
        a = self.agent
        out = {}
        if a.sampler is not None:
            st = a.sampler.stats
            out["oncpu"] = {"samples": st.samples, "emits": st.emits}
        if a.tpuprobe is not None:
            out["tpuprobe"] = dict(a.tpuprobe.stats)
        for ep in a.extprofilers:
            out[f"extprof-{ep.pid}"] = {"samples": ep.stats.samples,
                                        "lost": ep.lost}
        return out

    def _spans(self, args):
        """The agent's last N finished spans (probe cycles and the
        frames they sent), newest last. args: [n]"""
        n = int(args[0]) if args else 64
        return {"spans": self.agent.recent_spans()[-n:] if n > 0 else []}

    def _pcap_capture(self, args):
        """On-demand raw capture shipped to the server (reference: pcap
        policy -> ingester pcap store). args: [seconds] [iface]
        [max_packets]. Runs inline on the sync thread (bounded seconds)."""
        import gzip
        import socket as _s
        import struct
        import time as _t

        seconds = min(float(args[0]) if args else 2.0, 30.0)
        iface = args[1] if len(args) > 1 else ""
        max_packets = min(int(args[2]) if len(args) > 2 else 2000,
                          100_000)  # bound agent memory
        try:
            sock = _s.socket(_s.AF_PACKET, _s.SOCK_RAW, _s.htons(0x0003))
        except (PermissionError, AttributeError, OSError) as e:
            return {"error": f"raw capture unavailable: {e}"}
        if iface:
            sock.bind((iface, 0))
        sock.settimeout(0.2)
        frames = []
        start_ns = _t.time_ns()
        deadline = _t.monotonic() + seconds
        try:
            while _t.monotonic() < deadline and len(frames) < max_packets:
                try:
                    frame, addr = sock.recvfrom(65535)
                except _s.timeout:
                    continue
                if addr[0] == "lo" and addr[2] == _s.PACKET_OUTGOING:
                    continue
                frames.append((frame, _t.time_ns()))
        finally:
            sock.close()
        buf = bytearray(struct.pack("<IHHiIII", 0xA1B2C3D4, 2, 4, 0, 0,
                                    65535, 1))
        for frame, ts in frames:
            buf += struct.pack("<IIII", ts // 1_000_000_000,
                               (ts % 1_000_000_000) // 1000,
                               len(frame), len(frame))
            buf += frame
        from deepflow_tpu.proto import pb as _pb
        up = _pb.PcapUpload()
        up.name = f"cap-{start_ns}"
        up.agent_id = self.agent.config.agent_id
        up.start_ns = start_ns
        up.packet_count = len(frames)
        up.pcap_gz = gzip.compress(bytes(buf))
        from deepflow_tpu.codec import MessageType
        self.agent.sender.send(MessageType.PCAP, up.SerializeToString())
        return {"name": up.name, "packets": len(frames),
                "bytes_gz": len(up.pcap_gz)}

    def _upgrade(self, args):
        """OTA upgrade (reference: agent.proto:9 Upgrade stream +
        cli/ctl/agent.go:135 repo rollout). Two modes:

        - no version arg: drain and re-exec, picking up updated code
          already on disk.
        - `version=vX` arg: DOWNLOAD that package from the controller
          repo over the sync plane, verify its sha256, unpack it into a
          versioned directory, and re-exec with the new tree FIRST on
          PYTHONPATH — binary distribution, not just restart.
        """
        if "dry-run" in args:
            return {"upgrading": False, "dry_run": True, "argv": sys.argv}
        version = ""
        for a in args:
            if a.startswith("version="):
                version = a.split("=", 1)[1]
        env_extra: dict[str, str] = {}
        staged = None
        if version:
            try:
                staged = self._stage_package(version)
            except Exception as e:  # noqa: BLE001 - report, don't die
                return {"upgrading": False, "error": str(e)}
            prior = os.environ.get("PYTHONPATH", "")
            env_extra["PYTHONPATH"] = (f"{staged}:{prior}" if prior
                                       else staged)

        def _reexec():
            log.warning("upgrade: re-exec %s (staged=%s)", sys.argv,
                        staged)
            sync = getattr(self.agent, "synchronizer", None)
            if sync is not None:
                try:
                    sync.sync_once()  # ship the upgrade's own result first
                except Exception:
                    pass
            try:
                self.agent.stop()
            except Exception:
                pass
            os.environ.update(env_extra)
            self._execv(sys.executable, [sys.executable] + sys.argv)

        threading.Timer(0.5, _reexec).start()
        return {"upgrading": True, "argv": sys.argv,
                "version": version or None, "staged": staged}

    def _stage_package(self, version: str) -> str:
        """Fetch + verify + unpack a repo package; returns the directory
        to prepend to PYTHONPATH."""
        import hashlib
        import tarfile
        import tempfile

        sync = getattr(self.agent, "synchronizer", None)
        if sync is None:
            raise RuntimeError("no controller connection for OTA fetch")
        resp = sync.fetch_package("agent", version)
        if not resp.found:
            raise RuntimeError(f"package agent@{version} not in repo")
        sha = hashlib.sha256(resp.data).hexdigest()
        if sha != resp.sha256:
            raise RuntimeError(
                f"package digest mismatch: {sha} != {resp.sha256}")
        base = os.environ.get("DF_UPGRADE_DIR") or os.path.join(
            tempfile.gettempdir(), "df-agent-versions")
        dest = os.path.join(base, resp.version)
        staging = dest + ".staging"
        import shutil
        shutil.rmtree(staging, ignore_errors=True)
        os.makedirs(staging, exist_ok=True)
        import io
        with tarfile.open(fileobj=io.BytesIO(resp.data), mode="r:gz") as t:
            # refuse path traversal / links (an OTA package is trusted
            # code by definition, but a corrupted archive must not write
            # outside its version directory)
            for m in t.getmembers():
                p = os.path.normpath(m.name)
                if p.startswith("..") or os.path.isabs(p) or \
                        m.issym() or m.islnk():
                    raise RuntimeError(f"unsafe member {m.name!r}")
            try:  # belt-and-braces on 3.12+; manual checks above are
                # the real guard (filter= absent before 3.10.12/3.11.4)
                t.extractall(staging, filter="data")
            except TypeError:
                t.extractall(staging)
        shutil.rmtree(dest, ignore_errors=True)
        os.replace(staging, dest)
        log.warning("upgrade: staged agent@%s at %s", resp.version, dest)
        return dest

    # test seam: replaced in tests so an 'upgrade' never re-execs pytest
    _execv = staticmethod(os.execv)


def load_plugins(module_paths: list[str]) -> list[str]:
    """Import parser plugins: each module exports PARSERS (L7Parser
    subclasses), registered ahead of the builtins so plugins can override
    (reference: wasm hooks run before native parsers)."""
    from deepflow_tpu.agent.protocol_logs.base import REGISTRY
    loaded = []
    for path in module_paths:
        try:
            mod = importlib.import_module(path)
            parsers = getattr(mod, "PARSERS", [])
            for cls in parsers:
                REGISTRY.insert(0, cls())
                loaded.append(f"{path}.{cls.__name__}")
        except Exception as e:
            log.warning("plugin %s failed to load: %s", path, e)
    if loaded:
        log.info("plugins loaded: %s", ", ".join(loaded))
    return loaded
