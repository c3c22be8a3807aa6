"""``heturun`` launcher: yaml cluster config -> PS/worker process fleet.

Reference parity: ``bin/heturun`` -> ``python/runner.py:148-270`` (yaml
``nodes:`` parsing, chief election, local fork vs ssh remote launch) and
``python/hetu/launcher.py:18-58`` (the in-process ``launch(target, args)``
API that forks scheduler/server/worker roles).

TPU-native differences:

* No scheduler process. The reference needs a ps-lite rendezvous scheduler
  (DMLC_PS_ROOT_URI); our PS transport is direct-addressed — the launcher
  computes every server's host:port up front and hands workers the full
  list via ``HETU_PS_HOSTS`` / ``HETU_PS_PORTS``.
* Multi-host workers are JAX processes in one SPMD job: the launcher
  elects the chief as the JAX coordinator and exports
  ``HETU_COORDINATOR`` / ``HETU_NUM_PROCS`` / ``HETU_PROC_ID``; the
  executor calls ``jax.distributed.initialize`` when it sees them
  (executor.maybe_init_distributed) so ICI/DCN collectives span hosts.

Config (same shape as the reference's):

.. code-block:: yaml

    nodes:
      - host: localhost
        servers: 1
        workers: 2
        chief: true
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

__all__ = ["parse_config", "launch", "launch_command", "run_autoplan",
           "main"]

_procs = []


def _load_yaml(path):
    try:
        import yaml
        with open(path) as f:
            return yaml.safe_load(f)
    except ImportError:
        # minimal fallback parser for the flat nodes schema above
        # (yaml is an optional dependency; configs are tiny)
        nodes, cur = [], None
        top = {}
        with open(path) as f:
            for raw in f:
                line = raw.split("#", 1)[0].rstrip()
                if not line.strip() or line.strip() == "nodes:":
                    continue
                stripped = line.strip()
                if stripped.startswith("- "):
                    cur = {}
                    nodes.append(cur)
                    stripped = stripped[2:]
                if ":" in stripped:
                    k, v = (x.strip() for x in stripped.split(":", 1))
                    if v.lower() in ("true", "false"):
                        v = v.lower() == "true"
                    elif v.isdigit():
                        v = int(v)
                    # unindented lines are top-level keys (e.g. spmd)
                    if line[0] not in " \t" and not line.startswith("- "):
                        top[k] = v
                    elif cur is not None:
                        cur[k] = v
        return {"nodes": nodes, **top}


class ClusterConfig:
    """Parsed cluster description (reference runner.py:158-186).

    ``spmd=True`` (yaml top-level ``spmd: true``) makes every worker a
    process of ONE JAX SPMD job even on a single machine — the hermetic
    form of the multi-host path (jax.distributed over localhost)."""

    def __init__(self, nodes, spmd=False):
        self.hosts = []
        self.servers = {}       # host -> count
        self.workers = {}       # host -> count
        self.chief = None
        self.spmd = bool(spmd)
        allowed = {"host", "servers", "workers", "chief"}
        for node in nodes:
            extra = set(node) - allowed
            assert not extra, f"invalid node attributes: {extra}"
            host = node["host"]
            self.hosts.append(host)
            if node.get("servers", 0):
                self.servers[host] = int(node["servers"])
            if node.get("workers", 0):
                self.workers[host] = int(node["workers"])
            if node.get("chief", False):
                assert self.chief is None, "there should be only one chief"
                self.chief = host
        assert self.chief is not None, "there should be one chief"

    @property
    def num_servers(self):
        return sum(self.servers.values())

    @property
    def num_workers(self):
        return sum(self.workers.values())

    @property
    def single_host(self):
        # ADVICE r2: a cluster with ONE remote host is not single-host —
        # ports probed here say nothing about where servers bind
        local = {"localhost", "127.0.0.1"}
        return set(self.hosts) <= local

    def server_endpoints(self, base_port=None):
        """[(host, port)] for every server.

        Single-host: probe free ports locally. Multi-host: probing the
        launcher machine says nothing about a remote host, so assign a
        deterministic contiguous range from ``base_port``
        (HETU_PS_BASE_PORT, default 18590) instead.
        """
        eps = []
        if self.single_host and base_port is None:
            from .ps.server import pick_free_port
            for host, n in self.servers.items():
                eps.extend((host, pick_free_port()) for _ in range(n))
            return eps
        port = base_port if base_port is not None else int(
            os.environ.get("HETU_PS_BASE_PORT", "18590"))
        for host, n in self.servers.items():
            for _ in range(n):
                eps.append((host, port))
                port += 1
        return eps

    def worker_hosts(self):
        """Worker hosts with the chief first: rank 0 must live on the
        chief because JAX process 0 hosts the coordinator service."""
        hosts = list(self.workers.items())
        hosts.sort(key=lambda kv: kv[0] != self.chief)
        return hosts


def parse_config(path):
    settings = _load_yaml(path)
    return ClusterConfig(settings["nodes"],
                         spmd=settings.get("spmd", False))


def _is_local(host):
    return host in ("localhost", "127.0.0.1")


def _ps_env(cfg, endpoints, backups=None):
    env = {}
    if endpoints:
        env["HETU_PS_HOSTS"] = ",".join(h for h, _ in endpoints)
        env["HETU_PS_PORTS"] = ",".join(str(p) for _, p in endpoints)
        env["HETU_PS_NWORKERS"] = str(cfg.num_workers)
    if backups:
        # clients fail over to these per-shard replicas (ps_client.cc)
        env["HETU_PS_BACKUP_HOSTS"] = ",".join(h for h, _ in backups)
        env["HETU_PS_BACKUP_PORTS"] = ",".join(str(p)
                                               for _, p in backups)
    return env


def _backup_endpoints(cfg, endpoints):
    """One backup endpoint per primary shard (HETU_PS_REPLICATE=1):
    single-host probes fresh free ports; multi-host extends the
    deterministic range past the primaries."""
    if os.environ.get("HETU_PS_REPLICATE", "0") in ("0", "", "false") \
            or not endpoints:
        return []
    if cfg.single_host:
        return cfg.server_endpoints()
    base = int(os.environ.get("HETU_PS_BASE_PORT", "18590"))
    return cfg.server_endpoints(base_port=base + len(endpoints))


def _spawn_one_server(cfg, host, port, senv, identify, pkg_root):
    """Fork (or ssh) one PS server process."""
    if _is_local(host):
        pypath = pkg_root + os.pathsep + os.environ.get(
            "PYTHONPATH", "")
        p = subprocess.Popen(
            [sys.executable, "-m", "hetu_tpu.ps.run_server",
             str(port), str(cfg.num_workers)],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": pypath, **senv})
    else:
        import shlex
        ssh = ["ssh"] + (["-i", identify] if identify else []) + [host]
        remote = " ".join(shlex.quote(a) for a in [
            "python3", "-m", "hetu_tpu.ps.run_server",
            str(port), str(cfg.num_workers)])
        exports = " ".join(f"{k}={shlex.quote(str(v))}"
                           for k, v in senv.items())
        # remote spawns need the package on PYTHONPATH too
        p = subprocess.Popen(
            ssh + [f"env PYTHONPATH={shlex.quote(pkg_root)} "
                   f"JAX_PLATFORMS=cpu {exports} {remote}"])
    _procs.append(p)
    return p


def _spawn_servers(cfg, endpoints, identify=None, extra_env=None):
    """Start every PS server (local fork; ssh for remote hosts).
    ``extra_env`` maps endpoint index -> env dict (telemetry scrape
    port per server; replication target for primaries). Returns one
    record per server — the watchdog's respawn handle."""
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if any(_is_local(h) for h, _ in endpoints):
        # one build before the fleet, not N racing lazy builds inside
        # the start-up deadline below (ps/server.py ensure_server)
        from .ps.native_lib import build_lib
        build_lib()
    servers = []
    for i, (host, port) in enumerate(endpoints):
        senv = (extra_env or {}).get(i, {})
        p = _spawn_one_server(cfg, host, port, senv, identify, pkg_root)
        servers.append({"proc": p, "host": host, "port": port,
                        "env": senv, "identify": identify,
                        "pkg_root": pkg_root})
    # wait for every endpoint to accept — remote ones included (a worker
    # whose PSClient connects before its server binds raises immediately)
    from .ps.server import _port_open
    deadline = time.time() + (15 if all(_is_local(h)
                                        for h, _ in endpoints) else 60)
    for host, port in endpoints:
        probe = "127.0.0.1" if _is_local(host) else host
        while not _port_open(probe, port):
            assert time.time() < deadline, \
                f"PS server {host}:{port} not up"
            time.sleep(0.05)
    return servers


def _cpu_pinned():
    """Workers inherit this environment: pinned to the CPU platform
    (tests, dev boxes) they neither share a chip nor want its cache."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"


def _local_tpu_chips():
    """TPU chips attached to this host, counted from the PCI bus the way
    JAX's own start-up does — without initialising a backend, because
    the launcher must never hold the chip its workers need."""
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def _refuse_shared_chip(cfg):
    """One process per chip: a TPU belongs to the first process that
    initialises the backend, and that process claims every chip of the
    host. ``workers: N > 1`` on one TPU host would start N processes
    that each try to, so all but one fail or hang. Refuse up front and
    point at the form that works — ONE worker process driving all local
    chips as a mesh (docs/tools.md, "One process per chip")."""
    if _cpu_pinned():
        return
    local = sum(n for host, n in cfg.worker_hosts() if _is_local(host))
    chips = _local_tpu_chips() if local > 1 else 0
    if chips:
        raise RuntimeError(
            f"heturun: {local} worker processes on this host would "
            f"each claim its {chips} TPU chip(s); a chip belongs to one "
            f"process. Use `workers: 1` and drive the chips from that "
            f"process as a device mesh (Executor(..., mesh=...) / "
            f"comm_mode='AllReduce' over jax.devices()), or pin the "
            f"workers to the CPU with JAX_PLATFORMS=cpu.")


def _worker_env(cfg, base_env, rank, coordinator=None,
                metrics_port=None):
    env = dict(base_env)
    env["HETU_PS_RANK"] = str(rank)
    if coordinator:
        # multi-host SPMD: executor calls jax.distributed.initialize
        env["HETU_COORDINATOR"] = coordinator
        env["HETU_NUM_PROCS"] = str(cfg.num_workers)
        env["HETU_PROC_ID"] = str(rank)
    if metrics_port:
        # per-rank /metrics + /fleet scrape (heturun --watch)
        env["HETU_METRICS_PORT"] = str(metrics_port)
    return env


def run_preflight(cfg, command):
    """Static preflight gate (``heturun --preflight``): run ``command``
    ONCE in a plain subprocess with ``HETU_PREFLIGHT`` set. The
    executor's config hook (executor.py) analyzes the graph the script
    builds, prints findings, and exits before any PS/worker machinery —
    no fleet env (coordinator, PS hosts) is exported, so a multi-host
    script preflights entirely on the launcher machine. Only the stage-
    ownership env (HETU_NUM_PROCS / HETU_HOSTS) is provided, so the
    deadlock pass maps stage hostnames to the ranks the real launch
    would use. Returns the subprocess's exit code: 0 = clean graph,
    analysis.EXIT_PREFLIGHT = findings rejected it, anything else = the
    script crashed before the verifier ran (equally a reason not to
    spawn the fleet)."""
    import tempfile
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hosts_in_order = []
    for host, n in cfg.worker_hosts():
        hosts_in_order.extend([host] * n)
    report_path = os.path.join(tempfile.mkdtemp(prefix="hetu-preflight-"),
                               "preflight.json")
    env = {**os.environ,
           "PYTHONPATH": pkg_root + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           "HETU_PREFLIGHT": report_path,
           "HETU_NUM_PROCS": str(max(1, cfg.num_workers))}
    if hosts_in_order:
        env["HETU_HOSTS"] = ",".join(hosts_in_order)
    for stale in ("HETU_COORDINATOR", "HETU_PS_HOSTS", "HETU_PS_PORTS",
                  "HETU_PROC_ID", "HETU_AUTOPLAN_REPORT"):
        env.pop(stale, None)
    p = subprocess.run(command, env=env)
    if p.returncode == 0:
        if os.path.exists(report_path):
            print(f"preflight: graph verified clean "
                  f"(report: {report_path})")
        else:
            # exit 0 without a report = the script finished without ever
            # constructing an Executor — nothing was actually verified
            print("preflight: WARNING script exited 0 but never built a "
                  "graph (no Executor constructed); nothing was verified")
    return p.returncode


def run_autoplan(cfg, command):
    """Cost-model plan preview (``heturun --autoplan``): run ``command``
    ONCE in a plain subprocess with ``HETU_AUTOPLAN_REPORT`` set — the
    executor's config hook (executor.py) runs the auto-parallelism
    planner over the graph the script builds, prints the chosen plan
    and its predicted-vs-measured cost table, writes the JSON report,
    and exits before any fleet machinery. Same fleet-env scrubbing as
    the preflight gate, and the same stage-ownership env so pp plans
    map hostnames the way the real launch would. Exit 0 = plan
    printed; anything else = the script crashed before an Executor was
    built."""
    import tempfile
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hosts_in_order = []
    for host, n in cfg.worker_hosts():
        hosts_in_order.extend([host] * n)
    report_path = os.path.join(tempfile.mkdtemp(prefix="hetu-autoplan-"),
                               "autoplan.json")
    env = {**os.environ,
           "PYTHONPATH": pkg_root + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           "HETU_AUTOPLAN_REPORT": report_path,
           "HETU_NUM_PROCS": str(max(1, cfg.num_workers))}
    if hosts_in_order:
        env["HETU_HOSTS"] = ",".join(hosts_in_order)
    for stale in ("HETU_COORDINATOR", "HETU_PS_HOSTS", "HETU_PS_PORTS",
                  "HETU_PROC_ID", "HETU_PREFLIGHT"):
        env.pop(stale, None)
    p = subprocess.run(command, env=env)
    if p.returncode == 0:
        if os.path.exists(report_path):
            print(f"autoplan: report written to {report_path}")
        else:
            print("autoplan: WARNING script exited 0 but no report "
                  "file appeared — either the script never built an "
                  "Executor, or the report path was unwritable (a "
                  "plan table printed above means the latter)")
    return p.returncode


def launch_command(cfg, command, identify=None, telemetry=None,
                   hang_timeout=None, health=None, watch=False):
    """Run ``command`` once per worker with the cluster env wired
    (the ``heturun -c conf.yml python train.py`` path).

    ``telemetry`` (a directory, from ``--telemetry``) turns the unified
    telemetry layer on fleet-wide: every worker exports per-rank Chrome
    trace + metrics files there (HETU_TELEMETRY), each PS server serves
    a Prometheus ``/metrics`` scrape (HETU_TELEMETRY_PORT), and after
    the workers exit the launcher merges the per-rank traces into ONE
    Perfetto-loadable ``trace_merged.json``.

    ``health`` (a HealthOptions spec string, from ``--health``) arms
    the training health monitor fleet-wide: every worker's executors
    resolve ``Executor(health_options=None)`` from the exported
    ``HETU_HEALTH``, write per-rank ``health_rank<r>.jsonl`` files into
    the telemetry dir, and trip the configured action ladder on
    nonfinite values / grad spikes / staleness violations
    (telemetry/health.py). Implies telemetry (the health doctor needs a
    directory to merge) — a temp dir is created when ``--telemetry``
    was not given.

    ``hang_timeout`` (seconds, from ``--hang-timeout``) arms the fleet
    watchdog: workers heartbeat per step into the telemetry dir
    (HETU_WATCHDOG_DIR); when any rank stalls past the timeout the
    launcher collects faulthandler stack dumps + flight-record dumps
    from every live rank, kills the fleet, and exits with the distinct
    watchdog code (telemetry/watchdog.py) — a hung pipeline becomes a
    diagnosed failure instead of an eternal CI timeout. The watchdog
    implies telemetry (a temp dir is created when ``--telemetry`` was
    not given).

    ``watch`` (from ``--watch``) arms the live fleet plane
    (telemetry/fleet.py): workers record per-step timelines
    (HETU_FLEET) and serve ``/fleet`` on a per-rank metrics port; the
    launcher runs a FleetMonitor that polls heartbeats + scrapes, and
    prints a refreshing straggler/drift dashboard while the fleet
    runs, persisting ``fleet_report.json``. Implies telemetry."""
    _refuse_shared_chip(cfg)
    endpoints = cfg.server_endpoints()
    server_env = {}
    tdir = None
    if watch and not telemetry:
        import tempfile
        telemetry = tempfile.mkdtemp(prefix="hetu-fleet-")
        print(f"fleet: --watch without --telemetry; timelines and the "
              f"fleet report go to {telemetry}")
    if hang_timeout and not telemetry:
        import tempfile
        telemetry = tempfile.mkdtemp(prefix="hetu-watchdog-")
        print(f"watchdog: --hang-timeout without --telemetry; black-box "
              f"dumps go to {telemetry}")
    if health and not telemetry:
        import tempfile
        telemetry = tempfile.mkdtemp(prefix="hetu-health-")
        print(f"health: --health without --telemetry; health records "
              f"go to {telemetry}")
    if telemetry:
        tdir = os.path.abspath(telemetry)
        os.makedirs(tdir, exist_ok=True)
        _clear_stale_blackbox(tdir)
        scrape_base = int(os.environ.get("HETU_TELEMETRY_BASE_PORT",
                                         "18790"))
        for i, (host, _) in enumerate(endpoints):
            server_env[i] = {"HETU_TELEMETRY_PORT": str(scrape_base + i),
                             # server faulthandler stacks land in the
                             # same dir the workers dump into
                             "HETU_TELEMETRY": tdir}
            print(f"telemetry: PS server {i} scrape at "
                  f"http://{host}:{scrape_base + i}/metrics")
    # replicated shards (HETU_PS_REPLICATE=1): backups come up first so
    # each primary can dial its replication target at startup; workers
    # learn both endpoint lists and fail over client-side
    backups = _backup_endpoints(cfg, endpoints)
    backup_recs = []
    if backups:
        backup_recs = _spawn_servers(cfg, backups, identify)
        for i, (bhost, bport) in enumerate(backups):
            server_env.setdefault(i, {}).update({
                "HETU_PS_MY_BACKUP_HOST": bhost,
                "HETU_PS_MY_BACKUP_PORT": str(bport)})
    servers = _spawn_servers(cfg, endpoints, identify,
                             extra_env=server_env)
    ps_env = _ps_env(cfg, endpoints, backups)
    if tdir:
        ps_env["HETU_TELEMETRY"] = tdir
    if health:
        # every worker's Executor resolves health_options from the env
        ps_env["HETU_HEALTH"] = str(health)
    metrics_ports = None
    if watch:
        ps_env["HETU_FLEET"] = "1"
        # live skew signal needs heartbeats even without --hang-timeout:
        # arm the heartbeat writer (the watchdog itself only fires when
        # hang_timeout is set)
        ps_env.setdefault("HETU_WATCHDOG_DIR", tdir)
        metrics_ports = {}
        if cfg.single_host:
            from .ps.server import pick_free_port
            for r in range(cfg.num_workers):
                metrics_ports[r] = pick_free_port()
        else:
            mbase = int(os.environ.get("HETU_METRICS_BASE_PORT",
                                       "18890"))
            for r in range(cfg.num_workers):
                metrics_ports[r] = mbase + r
            print("fleet: WARNING multi-host fleet — /fleet scrapes "
                  "and flushed timelines cover launcher-local ranks "
                  "only; remote ranks contribute heartbeat signal "
                  "written on their own filesystem")
    if hang_timeout:
        ps_env["HETU_WATCHDOG_DIR"] = tdir
        ps_env["HETU_HANG_TIMEOUT"] = str(float(hang_timeout))
        if not cfg.single_host:
            # remote ranks heartbeat/dump on THEIR filesystem and the
            # diagnose signals hit the local ssh client, which does not
            # forward them — same scope caveat as the trace merge
            print("watchdog: WARNING multi-host fleet — stall detection "
                  "and stack/flight dumps cover launcher-local ranks "
                  "only; remote ranks are torn down via their ssh "
                  "clients without dumps")
    coordinator = None
    if not cfg.single_host or cfg.spmd:
        # deterministic port: probing the launcher machine says nothing
        # about the chief; rank 0 (on the chief) serves the coordinator
        chief = ("127.0.0.1" if cfg.single_host else cfg.chief)
        coordinator = "{}:{}".format(
            chief, os.environ.get("HETU_COORDINATOR_PORT", "29400"))
        # pipeline p2p channel addressing: one endpoint per worker rank
        # (hetu_tpu/parallel/p2p.py), and the hostname->rank map used
        # for stage ownership (pipeline._owner_of). Only a single-host
        # cluster may rewrite to loopback — in a mixed cluster a remote
        # rank dialing "127.0.0.1" for a local rank would dial itself;
        # multi-host clusters need cluster-routable hostnames as-is.
        whosts, hosts_in_order = [], []
        for host, n in cfg.worker_hosts():
            pipe_host = ("127.0.0.1" if cfg.single_host else host)
            whosts.extend([pipe_host] * n)
            hosts_in_order.extend([host] * n)
        ps_env["HETU_PIPE_HOSTS"] = ",".join(whosts)
        ps_env.setdefault("HETU_PIPE_BASE_PORT", os.environ.get(
            "HETU_PIPE_BASE_PORT", "19500"))
        ps_env["HETU_HOSTS"] = ",".join(hosts_in_order)

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pypath = pkg_root + os.pathsep + os.environ.get("PYTHONPATH", "")
    cache_env = {}
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ and not _cpu_pinned():
        # workers are chip entry points: hand them the fixed in-checkout
        # compile cache unless one was placed from outside
        # (hetu_tpu/cachedir.py)
        from .cachedir import STATE_ROOT
        cache_env["JAX_COMPILATION_CACHE_DIR"] = STATE_ROOT
    workers = []
    rank = 0
    for host, n in cfg.worker_hosts():   # chief first: rank 0 on chief
        for _ in range(n):
            wenv = _worker_env(
                cfg, ps_env, rank, coordinator,
                metrics_port=(metrics_ports or {}).get(rank))
            wenv["PYTHONPATH"] = pypath
            wenv.update(cache_env)
            if _is_local(host):
                p = subprocess.Popen(command,
                                     env={**os.environ, **wenv})
            else:
                import shlex
                ssh = ["ssh"] + (["-i", identify] if identify else [])
                exports = " ".join(
                    f"{k}={shlex.quote(str(v))}"
                    for k, v in wenv.items())
                quoted = " ".join(shlex.quote(c) for c in command)
                p = subprocess.Popen(
                    ssh + [host, f"env {exports} {quoted}"])
            workers.append(p)
            _procs.append(p)
            rank += 1

    if hang_timeout or watch:
        rc = _wait_with_watchdog(workers, tdir,
                                 float(hang_timeout or 0.0),
                                 servers=servers + backup_recs, cfg=cfg,
                                 watch=watch,
                                 metrics_ports=metrics_ports)
    else:
        rc = 0
        for p in workers:
            p.wait()
            rc = rc or p.returncode
    _shutdown()
    if tdir:
        _merge_telemetry(tdir, cfg.num_workers)
    return rc


def _respawn_dead_servers(servers, cfg):
    """In-job PS failover, launcher side: a dead server process is NOT
    a fleet failure — clients flip to the shard's other replica and
    replay their acked-push window (ps_client.cc), so the launcher just
    respawns a fresh standby on the same endpoint (it rejoins empty;
    the one-way client flip never reads it, but a later death of the
    surviving replica has somewhere to forward to)."""
    for srec in servers or []:
        p = srec["proc"]
        if p.poll() is None:
            continue
        host, port = srec["host"], srec["port"]
        if not _is_local(host):
            print(f"watchdog: PS server {host}:{port} exited "
                  f"rc={p.returncode}; remote respawn unsupported — "
                  f"clients run on the surviving replica")
            srec["proc"] = subprocess.Popen(["true"])   # stop re-firing
            continue
        print(f"watchdog: PS server {host}:{port} exited "
              f"rc={p.returncode} — respawning standby (clients fail "
              f"over to the backup replica and replay)")
        srec["proc"] = _spawn_one_server(
            cfg, host, port, srec["env"], srec["identify"],
            srec["pkg_root"])


def _make_fleet_monitor(workers, tdir, metrics_ports):
    """Launcher-side FleetMonitor (heturun --watch): its Telemetry has
    NO out_dir on purpose — the monitor must not install crash handlers
    or atexit flushes in the launcher process; its fleet_watch/drift
    trace is exported explicitly to ``trace_fleet.json``."""
    from .telemetry import Telemetry
    from .telemetry.fleet import FleetMonitor
    mtel = Telemetry(enabled=True, rank=len(workers) + 900,
                     service="fleet-monitor")
    return FleetMonitor(
        tdir, num_workers=len(workers), metrics_ports=metrics_ports,
        telemetry=mtel,
        out_path=os.path.join(tdir, "fleet_report.json"))


def _finish_fleet_monitor(monitor, tdir, show=True):
    """Final forced poll + report + trace export (normal exit AND the
    watchdog-fire path — the last window is the interesting one)."""
    from .telemetry.fleet import render_report
    try:
        rep = monitor.poll(force=True)
        if rep is not None and show:
            print(render_report(rep), flush=True)
        monitor.tel.tracer.export(os.path.join(tdir, "trace_fleet.json"))
        print(f"fleet: report -> "
              f"{os.path.join(tdir, 'fleet_report.json')}")
    except Exception as e:     # noqa: BLE001 — monitoring must not
        print(f"fleet: WARNING final report failed: {e}")   # kill rc


def _wait_with_watchdog(workers, tdir, hang_timeout, servers=None,
                        cfg=None, watch=False, metrics_ports=None):
    """Poll the fleet under the watchdog and/or the live fleet monitor:
    normal completion returns the usual first-nonzero rc; a stalled
    rank triggers the diagnose-then-kill sequence and the distinct
    watchdog exit code. A dead PS server is survivable (replicated
    shards) — it respawns instead of failing the fleet. With ``watch``
    the FleetMonitor refreshes the straggler/drift dashboard between
    checks (throttled internally to its polling interval)."""
    from .telemetry.fleet import render_report
    from .telemetry.watchdog import FleetWatchdog
    wd = None
    if hang_timeout:
        wd = FleetWatchdog(tdir, num_workers=len(workers),
                           timeout=hang_timeout)
    monitor = _make_fleet_monitor(workers, tdir, metrics_ports) \
        if watch else None
    by_rank = dict(enumerate(workers))
    poll_s = min(0.25, hang_timeout / 8) if hang_timeout else 0.25
    while any(p.poll() is None for p in workers):
        if cfg is not None:
            _respawn_dead_servers(servers, cfg)
        if monitor is not None:
            rep = monitor.poll()    # None between windows (throttled)
            if rep is not None:
                print(render_report(rep), flush=True)
        if wd is not None:
            stalled = wd.check(by_rank)
            if stalled:
                for rank, age, step in stalled:
                    print(f"watchdog: rank {rank} stalled "
                          f"{age:.1f}s > {hang_timeout:.1f}s "
                          f"(last step {step}) — collecting stack + "
                          f"flight dumps, killing fleet")
                rc = wd.fire(by_rank)
                if monitor is not None:
                    # the window right before the kill is the evidence
                    _finish_fleet_monitor(monitor, tdir)
                print(f"watchdog: fleet killed; post-mortem with "
                      f"`python -m hetu_tpu.telemetry.blackbox {tdir}` "
                      f"(exit code {rc})")
                return rc
        time.sleep(poll_s)
    if monitor is not None:
        _finish_fleet_monitor(monitor, tdir)
    rc = 0
    for p in workers:
        rc = rc or p.returncode
    return rc


def _clear_stale_blackbox(tdir):
    """Drop a previous fleet's heartbeats / flight dumps / stack logs /
    health records from a reused --telemetry dir. A stale hb_rank*.json
    with an old timestamp would false-fire the watchdog on the
    brand-new healthy fleet within its first poll, stale flight dumps
    would pollute the new run's blackbox report, and health_rank*.jsonl
    is append-mode — a reused dir would merge two runs' step
    numbering in the divergence doctor."""
    import glob as _glob
    for pat in ("hb_rank*.json", "flight_rank*.json", "stacks_*.log",
                "oom_rank*.txt", "health_rank*.jsonl",
                "health_lastgood_rank*.json", "timeline_rank*.jsonl",
                "fleet_report.json", "trace_fleet.json"):
        for path in _glob.glob(os.path.join(tdir, pat)):
            try:
                os.remove(path)
            except OSError:
                pass


def _merge_telemetry(tdir, num_workers=None):
    """Merge per-rank traces into one validated Perfetto file (best
    effort: a worker that never built an Executor exports nothing).
    Warns when fewer rank files exist than workers — remote-host ranks
    write on THEIR filesystem, so a multi-host merge here only covers
    the launcher-local ranks."""
    import glob as _glob
    from .telemetry import merge_traces
    from .telemetry.check import validate
    ranks = _glob.glob(os.path.join(tdir, "trace_rank*.json"))
    if num_workers and len(ranks) < num_workers:
        print(f"telemetry: WARNING only {len(ranks)}/{num_workers} "
              f"rank traces present under {tdir} — remote workers "
              f"export on their own filesystem; the merged trace "
              f"covers launcher-local ranks only")
    try:
        merged = merge_traces(tdir)
    except ValueError as e:
        print(f"telemetry: no traces to merge ({e})")
        return None
    n, errors = validate(merged)
    if errors:
        print(f"telemetry: merged trace INVALID: {errors[:3]}")
    else:
        print(f"telemetry: merged trace -> {merged} ({n} events; load "
              f"it at https://ui.perfetto.dev)")
    return merged


def _launch_worker(target, args, wenv):
    # module-level so the 'spawn' context can pickle it
    os.environ.update(wenv)
    if not _cpu_pinned():
        from .cachedir import enable_compile_cache
        enable_compile_cache()
    target(args)


def launch(target, args):
    """In-process API parity with reference launcher.py:18-38: fork
    ``launch.worker`` copies of ``target(args)`` locally with the PS
    fleet from ``args.config`` running. ``target`` must be a module-level
    function (it crosses a 'spawn' process boundary)."""
    import multiprocessing as mp
    cfg = parse_config(args.config)
    _refuse_shared_chip(cfg)
    endpoints = cfg.server_endpoints()
    _spawn_servers(cfg, endpoints)
    ps_env = _ps_env(cfg, endpoints)

    ctx = mp.get_context("spawn")
    ps = [ctx.Process(target=_launch_worker,
                      args=(target, args, _worker_env(cfg, ps_env, r)))
          for r in range(cfg.num_workers)]
    for p in ps:
        p.start()
    for p in ps:
        p.join()
    _shutdown()


def _shutdown(*_a):
    for p in _procs:
        if p.poll() is None:
            p.terminate()
    for p in _procs:
        try:
            p.wait(timeout=3)
        except Exception:
            p.kill()
    _procs.clear()


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="heturun",
        description="launch a hetu-tpu PS/worker cluster from yaml")
    parser.add_argument("-c", "--config", required=True,
                        help="cluster yaml (nodes: host/servers/workers)")
    parser.add_argument("-i", "--identify", default=None,
                        help="ssh identity file for remote hosts")
    # DIR is required (no nargs="?"): an optional value in front of the
    # REMAINDER command would swallow the command's first token as the
    # directory ("--telemetry python train.py" -> DIR "python")
    parser.add_argument("--telemetry", default=None, metavar="DIR",
                        help="enable the unified telemetry layer: "
                             "per-rank Chrome traces + metrics JSONL "
                             "under DIR, merged into one Perfetto "
                             "trace at exit; PS servers serve "
                             "Prometheus /metrics")
    parser.add_argument("--preflight", action="store_true",
                        help="static graph verification only: run the "
                             "command once on this machine with the "
                             "hetu_tpu.analysis passes armed, print "
                             "findings, and exit WITHOUT spawning "
                             "PS servers or workers (exit 0 clean, "
                             "121 on errors)")
    parser.add_argument("--autoplan", action="store_true",
                        help="cost-model plan preview: run the command "
                             "once with the auto-parallelism planner "
                             "armed (HETU_AUTOPLAN_REPORT), print the "
                             "chosen (dp,tp,pp,M,V) plan and its "
                             "predicted-vs-measured cost table, and "
                             "exit WITHOUT spawning the fleet")
    parser.add_argument("--health", default=None, metavar="SPEC",
                        help="arm the training health monitor fleet-"
                             "wide (exports HETU_HEALTH=SPEC): device-"
                             "side numerics sentinels + staleness "
                             "telemetry per rank, health_rank<r>.jsonl "
                             "under the telemetry dir, trip ladder per "
                             "SPEC (e.g. '1' or "
                             "'every_n=5,action=dump'); post-mortem "
                             "with python -m hetu_tpu.telemetry.health")
    parser.add_argument("--watch", action="store_true",
                        help="arm the live fleet plane: per-rank step "
                             "timelines + /fleet scrape endpoints, a "
                             "launcher-side monitor printing a "
                             "refreshing straggler/victim dashboard "
                             "with CostDB drift verdicts, and "
                             "fleet_report.json in the telemetry dir "
                             "(post-hoc: python -m "
                             "hetu_tpu.telemetry.fleet DIR)")
    parser.add_argument("--hang-timeout", type=float, default=None,
                        metavar="SECONDS",
                        help="arm the fleet watchdog: when any rank's "
                             "heartbeat stalls past SECONDS, dump "
                             "stacks + flight records on every rank "
                             "and kill the fleet with a distinct exit "
                             "code (set it above worst-case compile "
                             "time)")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="worker command, e.g. python train.py")
    args = parser.parse_args(argv)
    assert args.command, "no worker command given"
    cfg = parse_config(args.config)
    print(f"Cluster: chief={cfg.chief} "
          f"servers({cfg.num_servers})={cfg.servers} "
          f"workers({cfg.num_workers})={cfg.workers}")
    signal.signal(signal.SIGINT, _shutdown)
    if args.preflight:
        return run_preflight(cfg, args.command)
    if args.autoplan:
        return run_autoplan(cfg, args.command)
    return launch_command(cfg, args.command, args.identify,
                          telemetry=args.telemetry,
                          hang_timeout=args.hang_timeout,
                          health=args.health, watch=args.watch)


if __name__ == "__main__":
    sys.exit(main())
