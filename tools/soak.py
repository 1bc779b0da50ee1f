#!/usr/bin/env python
"""Scripted SLO soak: open-loop load + staged chaos + burn-rate verdict.

Composes the ``nnstreamer_tpu.slo`` harness end to end:

1. **Target** — either an existing ``QueryServer`` (``--host/--port``)
   or, with ``--demo`` (default when no port is given), a loopback
   serving pipeline built in-process (``tensor_query_serversrc !
   tensor_transform ! tensor_query_serversink``) with span recording
   enabled so the flight recorder has a timeline to dump.
2. **Infra gate** — a staged TCP liveness check of the target
   (:func:`diagnose_endpoint`): a dead target yields a ``status:
   infra_dead`` verdict row and exit 2, never a FAIL that would read as
   a regression.
3. **Chaos** — a ``testing/faults.py`` :class:`ChaosProxy` between the
   clients and the server, driven by a staged
   :class:`ChaosSchedule` (``--chaos "21:kill;36:disconnect_once"``).
4. **Load** — ``slo/loadgen.py`` open-loop Poisson/constant arrivals
   over ``--clients`` concurrent query connections.
5. **Gate** — ``slo/evaluator.py`` multi-window burn rates against the
   ``--slo`` spec (default: the demo spec scaled to ``--duration``),
   with the flight recorder armed on breach onset.

Prints ONE verdict JSON line (plus a ``verdict.json`` artifact under
``--out``); exit 0 = PASS, 1 = FAIL, 2 = infra dead.

The acceptance demo::

    python tools/soak.py --demo            # 64 clients x 60 s, chaos on
    python tools/soak.py --demo --force-breach   # prove the recorder

``--force-breach`` adds an impossible latency objective (1 µs) so the
breach path — burn-rate alert, flight-recorder bundle with the
breaching window's spans — is exercised on demand.

``--overload FACTOR`` is the overload-protection acceptance run
(query/overload.py): a short closed-loop burst measures the target's
capacity, then the open-loop loadgen offers ``FACTOR``× that with
per-client QoS classes gold:silver:bronze weighted 1:2:5, against the
shedding-enabled server.  The verdict gains an ``overload`` section
asserting the admission invariants: admitted-traffic p99 holds the SLO
while the bronze shed-rate absorbs the excess, the incoming queue and
RSS stay bounded, every refused request got an explicit ``T_SHED``
(client-observed sheds == server shed counters, no silent drops), and
no circuit breaker tripped (shed is not failure).
"""

import argparse
import json
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))   # repo root: nnstreamer_tpu
sys.path.insert(0, _HERE)                    # sibling tools

DEMO_CAPS = ("other/tensors,format=static,num_tensors=1,dimensions=4,"
             "types=float32,framerate=0/1")
DEMO_SERVER_ID = 91


def _diagnose_once(host: str, port: int, timeout: float,
                   stages: dict) -> "str | None":
    """One staged pass over a TCP endpoint; fills ``stages`` and
    returns the name of the FIRST failed stage (or None when healthy).
    Stages name what broke:

    - ``dns``        — name resolution
    - ``connect``    — TCP dial
    - ``rtt``        — T_PING/T_PONG round trips over the query
      protocol (fails on a port that accepts but isn't a live
      ``QueryServer`` — the half-up failure mode)
    - ``throughput`` — one 256 KiB ping payload echo (the server echoes
      ping payloads), a bulk-bytes sanity number
    """
    import socket
    import time as _time

    def _ms(t0):
        return round((_time.monotonic() - t0) * 1e3, 2)

    t0 = _time.monotonic()
    try:
        infos = socket.getaddrinfo(str(host), int(port),
                                   type=socket.SOCK_STREAM)
    except OSError as exc:
        stages["dns"] = {"ok": False, "ms": _ms(t0),
                         "error": f"{type(exc).__name__}: {exc}"[:200]}
        return "dns"
    stages["dns"] = {"ok": True, "ms": _ms(t0), "addrs": len(infos)}

    t0 = _time.monotonic()
    try:
        sock = socket.create_connection((str(host), int(port)),
                                        timeout=timeout)
    except OSError as exc:
        stages["connect"] = {"ok": False, "ms": _ms(t0),
                             "error":
                                 f"{type(exc).__name__}: {exc}"[:200]}
        return "connect"
    stages["connect"] = {"ok": True, "ms": _ms(t0)}

    from nnstreamer_tpu.query.protocol import (Message, T_PING, T_PONG,
                                               recv_msg, send_msg,
                                               shutdown_close)

    try:
        sock.settimeout(timeout)

        def _ping(payload: bytes, seq: int) -> float:
            t = _time.monotonic()
            send_msg(sock, Message(T_PING, seq=seq, payload=payload))
            msg = recv_msg(sock)
            if msg is None or msg.type != T_PONG or msg.seq != seq:
                raise ConnectionError("no matching T_PONG "
                                      "(not a live QueryServer?)")
            return _time.monotonic() - t

        t0 = _time.monotonic()
        try:
            rtts = [_ping(b"", seq) for seq in (1, 2, 3)]
        except (OSError, ValueError, ConnectionError) as exc:
            stages["rtt"] = {"ok": False, "ms": _ms(t0),
                             "error":
                                 f"{type(exc).__name__}: {exc}"[:200]}
            return "rtt"
        stages["rtt"] = {"ok": True,
                         "rtt_ms_p50": round(sorted(rtts)[1] * 1e3, 2)}

        blob = b"\x5a" * (256 << 10)
        t0 = _time.monotonic()
        try:
            took = _ping(blob, 4)
        except (OSError, ValueError, ConnectionError) as exc:
            stages["throughput"] = {
                "ok": False, "ms": _ms(t0),
                "error": f"{type(exc).__name__}: {exc}"[:200]}
            return "throughput"
        stages["throughput"] = {
            "ok": True,
            "MBps": round(2 * len(blob) / (1 << 20) / max(took, 1e-9),
                          2)}
        return None
    finally:
        shutdown_close(sock)


def diagnose_endpoint(host: str, port: int, timeout: float = 2.0,
                      retries: int = 0, backoff: float = 1.0) -> dict:
    """Structured liveness diagnosis of a ``QueryServer`` endpoint: the
    returned dict names the exact stage that failed
    (dns/connect/rtt/throughput) instead of a bare refused-connection
    string.  ``retries``/``backoff`` retry the whole staged pass with
    exponential spacing (a soak launched while a server restarts should
    wait out the restart, not report it dead)."""
    import time as _time

    out = {"metric": "endpoint_diagnosis", "target": f"{host}:{port}",
           "ok": False, "stage_failed": None, "attempts": 0,
           "stages": {}}
    for attempt in range(max(0, int(retries)) + 1):
        out["attempts"] = attempt + 1
        out["stages"] = {}
        out["stage_failed"] = _diagnose_once(host, int(port),
                                             float(timeout),
                                             out["stages"])
        if out["stage_failed"] is None:
            out["ok"] = True
            return out
        if attempt <= retries - 1:
            _time.sleep(min(30.0, float(backoff) * (2 ** attempt)))
    return out



def _register_delay_element():
    """``soak_delay ms=N``: a fixed per-frame service time for the demo
    serving pipeline.  The overload demo needs a server whose capacity
    the (GIL-bound, in-process) load harness can genuinely exceed 2x —
    the raw loopback transform is so fast that "2x capacity" would
    saturate the CLIENT side first and the schedule-anchored latency
    would measure the harness's own lag, not the server's protection."""
    import time as _time

    from nnstreamer_tpu.pipeline.element import Element, FlowReturn
    from nnstreamer_tpu.pipeline.registry import register_element
    from nnstreamer_tpu.tensor.caps_util import tensors_template_caps

    @register_element
    class SoakDelay(Element):
        """Fixed per-frame service delay (overload-demo element)."""

        FACTORY = "soak_delay"
        PROPERTIES = {"ms": (10.0, "per-frame service time, ms")}

        def _make_pads(self):
            self.add_sink_pad(tensors_template_caps(), "sink")
            self.add_src_pad(tensors_template_caps(), "src")

        def chain(self, pad, buf):
            _time.sleep(float(self.ms) / 1e3)
            return self.push(buf)

    return SoakDelay


def build_demo_server(server_id: int = DEMO_SERVER_ID,
                      queue_depth: int = 0, service_ms: float = 0.0):
    """Loopback serving pipeline with span recording on; returns
    ``(pipeline, data_port, tracer)``.  ``queue_depth`` sizes the
    server's bounded incoming queue (0 = element default) and
    ``service_ms`` inserts a fixed per-frame service time; the overload
    demo uses both — a latency-budget-sized bound (depth × service
    time ≤ the SLO's p99 threshold) so shedding, not queueing, absorbs
    the excess, over a service time slow enough that 2x its capacity is
    honestly offerable by the in-process harness."""
    from nnstreamer_tpu import parse_launch

    extra = f"queue-depth={queue_depth} " if queue_depth else ""
    delay = ""
    if service_ms > 0:
        _register_delay_element()
        delay = f"soak_delay ms={service_ms} ! "
    p = parse_launch(
        f"tensor_query_serversrc name=qsrc id={server_id} port=0 "
        f"{extra}caps={DEMO_CAPS} ! {delay}"
        "tensor_transform mode=arithmetic option=mul:2 ! "
        f"tensor_query_serversink id={server_id}")
    tracer = p.enable_tracing(spans=True)
    p.play()
    return p, p.get("qsrc").bound_port, tracer


def measure_capacity(host: str, port: int, seconds: float = 2.0,
                     concurrency: int = 8, payload=None) -> float:
    """Closed-loop capacity probe: ``concurrency`` connections issuing
    queries back-to-back measure the serving path's sustainable
    CONCURRENT rate — the capacity the overload factor multiplies.  A
    single-stream probe overstates it (no GIL/scheduler contention from
    a client population), and the whole point of "2x capacity" is that
    the admitted tiers' demand must fit under what the server really
    sustains.  Gold class, and concurrency stays under the gold
    watermark, so the probe itself is never shed."""
    import numpy as np

    from nnstreamer_tpu.obs.clock import mono_ns
    from nnstreamer_tpu.query.client import QueryConnection
    from nnstreamer_tpu.tensor.buffer import TensorBuffer

    import threading

    if payload is None:
        payload = np.arange(4, dtype=np.float32)
    counts = [0] * concurrency
    stop = threading.Event()

    def _probe(i):
        conn = QueryConnection(host, port, timeout=5.0, qos="gold")
        conn.connect()
        try:
            while not stop.is_set():
                conn.query(TensorBuffer(tensors=[payload]))
                counts[i] += 1
        except (ConnectionError, TimeoutError, OSError):
            pass
        finally:
            conn.close()

    threads = [threading.Thread(target=_probe, args=(i,), daemon=True)
               for i in range(concurrency)]
    t0 = mono_ns() / 1e9
    for t in threads:
        t.start()
    stop.wait(seconds)        # bounded run, event-driven
    stop.set()
    for t in threads:
        t.join(timeout=10)
    dt = max(1e-9, mono_ns() / 1e9 - t0)
    return sum(counts) / dt


class BreakerProbe:
    """Bronze :class:`FailoverConnection` issuing paced queries during
    the overload run.  The loadgen drives bare ``QueryConnection``s (no
    breakers anywhere), so without this probe a "no breaker trips"
    check would be vacuously true — the probe puts a real
    CircuitBreaker in the shed path, counts the sheds IT experienced,
    and reports its breaker's final state.  shed-is-not-failure is only
    proven when ``sheds > 0`` and the breaker stayed ``closed``."""

    def __init__(self, host: str, port: int, period_s: float = 0.25):
        import threading

        from nnstreamer_tpu.query.client import FailoverConnection
        from nnstreamer_tpu.query.resilience import RetryPolicy

        self.period_s = period_s
        self.sheds = 0
        self.ok = 0
        self.errors = 0
        self._stop = threading.Event()
        self._fc = FailoverConnection(
            [(host, port)], timeout=5.0,
            retry=RetryPolicy(max_attempts=1), qos="bronze")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="breaker-probe")

    def _loop(self):
        import numpy as np

        from nnstreamer_tpu.query.overload import ShedError
        from nnstreamer_tpu.tensor.buffer import TensorBuffer

        try:
            self._fc.connect()
        except ConnectionError:
            pass
        payload = np.arange(4, dtype=np.float32)
        while not self._stop.wait(self.period_s):
            try:
                self._fc.query(TensorBuffer(tensors=[payload]))
                self.ok += 1
            except ShedError:
                self.sheds += 1
            except (ConnectionError, TimeoutError, OSError):
                self.errors += 1

    def start(self) -> "BreakerProbe":
        self._thread.start()
        return self

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join(timeout=10)
        state = self._fc.breakers[0].state
        self._fc.close()
        return {"sheds": self.sheds, "ok": self.ok,
                "errors": self.errors, "breaker_state": state}


def overload_checks(server, summary, breaker_opens_delta: int,
                    rss_before_kb: int, slo_pass: bool,
                    probe: dict) -> dict:
    """The overload acceptance invariants, each reported with its
    evidence; ``pass`` is their conjunction (+ the SLO verdict on
    admitted traffic)."""
    import gc
    import resource

    from nnstreamer_tpu.tensor.buffer import default_pool

    gc.collect()   # promptly reclaim dropped leases before the pool read
    pool = default_pool().stats
    counters = server.counters()
    srv_shed = sum(counters["shed"].values())
    cli_shed = summary.get("shed", 0)
    rss_after_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    checks = {
        "queue_bounded": server.peak_depth <= server.queue_depth,
        # probe sheds ride the SAME wire bookkeeping (the probe's
        # FailoverConnection wraps a QueryConnection, so its sheds
        # land in the loadgen-independent server counters)
        "sheds_all_explicit": srv_shed == cli_shed + probe["sheds"],
        # non-vacuous: a breaker-carrying client SAW sheds and its
        # breaker stayed closed, plus zero global breaker transitions
        "no_breaker_trips": (breaker_opens_delta == 0
                             and probe["breaker_state"] == "closed"
                             and probe["sheds"] > 0),
        "no_leaked_slabs": pool["pending"] == 0,
        "admitted_slo_pass": bool(slo_pass),
    }
    return {
        "checks": checks, "pass": all(checks.values()),
        "server_counters": counters,
        "breaker_probe": probe,
        "client_sheds": cli_shed,
        "shed_by_class": summary.get("shed_by_class", {}),
        "shed_fraction": summary.get("shed_fraction", 0.0),
        "peak_incoming_depth": server.peak_depth,
        "queue_depth": server.queue_depth,
        "pool": pool,
        "breaker_opens": breaker_opens_delta,
        "rss_before_kb": rss_before_kb, "rss_after_kb": rss_after_kb,
        "rss_growth_mb": round((rss_after_kb - rss_before_kb) / 1024, 1),
    }


def demo_rate_from_capacity(capacity_rps: float, clients: int) -> float:
    """Satellite fix: the demo's offered rate self-sizes at ~50 % of the
    MEASURED concurrent capacity (the ``--overload`` 8-conn closed-loop
    probe), replacing the old hard-coded ~2 ms/query single-stream
    constant — which overstated per-frame capacity (no GIL/scheduler
    contention) and meant nothing at all for a batching server, whose
    capacity is a multiple of per-frame.  Returns arrivals/s PER
    CLIENT, floored so a pathological probe still offers traffic."""
    return max(0.05, 0.5 * capacity_rps / max(1, clients))


XBATCH_SERVER_ID = 92
#: the PR 8 profile's streaming baseline the --xbatch gate compares
#: against: admission-wait share of per-frame streaming e2e (a CPU host)
R08_ADMISSION_WAIT_PCT = 82.55

XBATCH_CAPS = ("other/tensors,format=static,num_tensors=1,dimensions=64,"
               "types=float32,framerate=0/1")
#: depth 32 x width 2048 (537 MB of weights): sized so the serving
#: regime the acceptance describes actually EXISTS on a 2-core CPU
#: host.  Per-frame serving is a ~60 ms GEMV that re-streams every
#: weight per frame — heavy enough that holding the demo SLO's 250 ms
#: latency objective forces the per-frame server to low utilization
#: (the r08 finding), while the batched bucket's GEMM reuses the
#: weights across rows and keeps a ~100 ms shared invoke inside the
#: same budget.  Lighter (depth 16) the 250 ms threshold stops biting
#: (a 24 ms GEMV holds it at 85% utilization) and the comparison
#: degenerates to raw capacity, which reply-path glue — not the device
#: — then bounds; heavier (depth 48) the weight-streaming floor of ONE
#: bucket invoke (~145 ms) already busts the two-cycle latency path no
#: matter the bucket size.
XBATCH_MLP = "custom=in_dim:64,width:2048,depth:32,out_dim:16"


def mlp_server_line(port: int, batch: int = 0,
                    timeout_ms: float = 0.0,
                    async_replies: bool = False) -> str:
    """Launch string for the loopback MLP serving pipeline (the
    batching-efficiency probe model, models/mlp.py — pure matmuls, so
    per-frame serving is a GEMV that re-streams every weight per frame
    while the batched bucket is a GEMM that reuses them).  ``batch=0``
    is the per-frame reference server; ``batch>1`` the cross-stream
    batching one.  ``async_replies`` moves the reply split onto the
    sink's ordered pusher thread so collect/invoke/split pipeline
    instead of serializing into one long bucket cycle — the serving
    configuration for the batching acceptance (without it the blame
    table shows invoke + sink + serialize summing to the whole cycle)."""
    xb = (f"batch={batch} batch-timeout-ms={timeout_ms} "
          if batch and batch > 1 else "")
    sink_props = " async-replies=true" if async_replies else ""
    return (f"tensor_query_serversrc name=qsrc id={XBATCH_SERVER_ID} "
            f"port={port} {xb}caps={XBATCH_CAPS} ! "
            f"tensor_filter name=f framework=xla model=mlp {XBATCH_MLP} "
            f"! tensor_query_serversink id={XBATCH_SERVER_ID}"
            f"{sink_props}")


def _free_port() -> int:
    import socket as _socket

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class ServerProc:
    """The serving pipeline as its OWN process (``launch.py --soak
    --profile --metrics-port``) — the ROADMAP item 5 follow-through:
    the single-process demo shares one GIL and two cores between the
    loadgen's client threads and the serving thread, so the very
    contention being generated suppresses the capacity being measured.
    Out of process, the server's GEMM gets the cores the GIL would have
    serialized, and its metrics/attribution arrive over the wire
    (/metrics scrapes) and as launch.py --profile artifacts."""

    def __init__(self, out_dir: str, batch: int = 0,
                 timeout_ms: float = 0.0, soak_s: float = 120.0,
                 env_extra=None, async_replies: bool = False,
                 profile: bool = True):
        import subprocess

        self.port = _free_port()
        self.metrics_port = _free_port()
        self.out_dir = out_dir
        os.makedirs(out_dir, exist_ok=True)
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env.update(env_extra or {})
        self.batch = batch
        self.cmd = [sys.executable, "-m", "nnstreamer_tpu.launch",
                    mlp_server_line(self.port, batch, timeout_ms,
                                    async_replies=async_replies),
                    "--soak", str(soak_s),
                    "--metrics-port", str(self.metrics_port)]
        if profile:
            # full span tracing halves serving-row throughput on small
            # CPU hosts (see PERFORMANCE.md observer-effect table) —
            # headline capacity/soak servers run unprofiled, the
            # attribution evidence comes from a SHORT traced pass (the
            # bench.py precedent: headline rows untraced, breakdown
            # from one traced pass)
            self.cmd += ["--profile", "--profile-out", out_dir]
        self._log = open(os.path.join(out_dir, "server.log"), "w",
                         encoding="utf-8")
        # repo root, not the caller's cwd: -m nnstreamer_tpu.launch
        # must resolve no matter where the soak was invoked from
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        self.proc = subprocess.Popen(self.cmd, stdout=self._log,
                                     stderr=self._log, env=env, cwd=root)

    def wait_ready(self, payload, timeout_s: float = 300.0) -> bool:
        """Block until the server has SERVED a round trip.  The data
        port accepts as soon as the serversrc starts, but the model may
        still be building/compiling for tens of seconds — a capacity
        probe against a still-compiling server measures the compiler,
        not the serving plane."""
        import time as _time

        import numpy as np

        from nnstreamer_tpu.query.client import QueryConnection
        from nnstreamer_tpu.tensor.buffer import TensorBuffer

        deadline = _time.monotonic() + timeout_s
        while _time.monotonic() < deadline:
            if self.proc.poll() is not None:
                return False
            try:
                conn = QueryConnection("127.0.0.1", self.port,
                                       timeout=60.0, max_retries=1)
                conn.connect()
                try:
                    out = conn.query(TensorBuffer(
                        tensors=[np.asarray(payload)]))
                    if out is not None:
                        return self._prime_buckets(payload)
                finally:
                    conn.close()
            except (ConnectionError, TimeoutError, OSError):
                _time.sleep(0.5)
        return False

    def _prime_buckets(self, payload, conns: int = 8,
                       rounds: int = 3) -> bool:
        """Cross-stream warmup: a lone readiness probe only exercises
        the SOLO fast path, so the padded-bucket executables
        (_jitexec.warmup_stacked — compiled lazily on the first bucket
        the filter sees) are still cold when wait_ready returns.  Force
        a multi-client bucket once, with a compile-sized timeout, so
        the first PROBED or SOAKED bucket is warm — otherwise every
        probe connection times out against a serving thread that is
        deep in XLA compiles for tens of seconds."""
        if self.batch <= 1:
            return True
        import threading as _threading

        import numpy as np

        from nnstreamer_tpu.query.client import QueryConnection
        from nnstreamer_tpu.tensor.buffer import TensorBuffer

        ok = [False] * conns

        def _drive(i):
            try:
                conn = QueryConnection("127.0.0.1", self.port,
                                       timeout=600.0, max_retries=1)
                conn.connect()
                try:
                    for _ in range(rounds):
                        conn.query(TensorBuffer(
                            tensors=[np.asarray(payload)]))
                    ok[i] = True
                finally:
                    conn.close()
            except (ConnectionError, TimeoutError, OSError):
                pass

        threads = [_threading.Thread(target=_drive, args=(i,),
                                     daemon=True) for i in range(conns)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=660)
        return any(ok)

    def scrape(self) -> dict:
        """One /metrics scrape parsed into {name{labels}: float}."""
        import re
        import urllib.request

        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.metrics_port}/metrics",
                    timeout=5) as resp:
                text = resp.read().decode("utf-8", "replace")
        except OSError:
            return {}
        out = {}
        for line in text.splitlines():
            if line.startswith("#") or " " not in line:
                continue
            key, _, val = line.rpartition(" ")
            try:
                out[key] = float(val)
            except ValueError:
                continue
        return out

    def metric(self, scraped: dict, name: str) -> float:
        for key, val in scraped.items():
            if key.startswith(name):
                return val
        return 0.0

    def profile(self) -> dict:
        import json as _json

        path = os.path.join(self.out_dir, "profile.json")
        try:
            with open(path, encoding="utf-8") as fh:
                return _json.load(fh)
        except (OSError, ValueError):
            return {}

    def stop(self, grace_s: float = 30.0) -> None:
        import signal

        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)   # graceful drain
        try:
            self.proc.wait(timeout=grace_s)
        except Exception:   # noqa: BLE001 — hard stop after the grace
            self.proc.kill()
            self.proc.wait(timeout=10)
        self._log.close()


def run_xbatch(args, ap) -> int:
    """Cross-stream batching acceptance run (the ROADMAP item 1 gate):

    1. serve the per-frame MLP pipeline in its own process
       (launch.py), measure its concurrent capacity (the 8-conn
       closed-loop probe);
    2. rebuild with ``batch=BUCKET`` (again out of process) and warm
       the padded-bucket executables;
    3. drive the PR 6 soak (64 clients, same SLO spec) from THIS
       process against the batching server at >= 4x the per-frame
       capacity;
    4. gate: SLO PASS at that load (>=4x rps at held latency), the
       server-side attribution's admission-wait share reduced from the
       PR 8 profile's 82.55 %, buckets actually formed, and zero pending
       pool slabs server-side.

    The verdict carries perf_diff-consumable ``rows`` (with the
    attribution block) so the regression gate can name the stage if the
    win ever erodes."""
    import time as _time

    import numpy as np

    from nnstreamer_tpu.slo import Evaluator, LoadGenerator, SLOMonitor, \
        load_spec

    bucket = int(args.xbatch)
    if bucket < 2:
        ap.error("--xbatch BUCKET must be >= 2")
    os.makedirs(args.out, exist_ok=True)
    clients = args.clients or 64
    duration = args.duration
    probe_payload = np.random.default_rng(7).standard_normal(
        64).astype(np.float32)

    spec = load_spec(args.slo, duration_s=duration)

    # 1. per-frame reference: its closed-loop capacity AND — the
    # baseline the 4x claim multiplies — the requests/s it sustains AT
    # HELD LATENCY under the same PR 6 soak.  Raw capacity is not a
    # latency-honest baseline: no server serves its closed-loop maximum
    # while holding a p99 objective, so the apples-to-apples comparison
    # is SLO-constrained goodput on BOTH sides.  The per-frame soak
    # offers 70% of measured capacity (a generous operating point; its
    # own verdict is recorded).  If the per-frame server CANNOT hold
    # the SLO even there, the raw closed-loop capacity becomes the
    # baseline instead — the gate never profits from a failed baseline
    # run.
    pf = ServerProc(os.path.join(args.out, "server_perframe"),
                    soak_s=900.0, profile=False)
    try:
        if not pf.wait_ready(probe_payload):
            print(json.dumps({"metric": "soak_xbatch", "pass": False,
                              "status": "infra_dead",
                              "vs_baseline": None,
                              "reason": "per-frame server never came "
                                        "up (see server.log)"}),
                  flush=True)
            return 2
        measure_capacity("127.0.0.1", pf.port, seconds=2.0,
                         payload=probe_payload)           # warm-up
        capacity_pf = measure_capacity("127.0.0.1", pf.port,
                                       seconds=4.0,
                                       payload=probe_payload)
        # held-SLO goodput search, stepping DOWN: 70% of capacity is a
        # generous per-frame operating point; if the latency objective
        # breaches there, retry at 45% then 30% before conceding the
        # baseline to raw closed-loop capacity (which is HIGHER than
        # any held-SLO goodput, so the fallback raises our own bar —
        # the gate never profits from a failed baseline run)
        pf_frac = 0.0
        for pf_frac in (0.7, 0.45, 0.3):
            pf_eval = Evaluator(spec)
            pf_monitor = SLOMonitor(pf_eval)
            pf_gen = LoadGenerator(
                "127.0.0.1", pf.port, clients=clients,
                rate_hz=pf_frac * capacity_pf / clients,
                duration_s=duration, schedule=args.schedule,
                seed=args.seed, timeout=max(args.timeout, 5.0),
                payload=probe_payload)
            pf_monitor.start()
            try:
                pf_summary = pf_gen.run()
            finally:
                pf_monitor.stop(final_tick=True)
            pf_verdict = pf_eval.verdict()
            pf_rps = pf_summary["ok"] / max(1e-9,
                                            pf_summary["duration_s"])
            if pf_verdict["pass"]:
                break
    finally:
        pf.stop()
    baseline_rps = pf_rps if pf_verdict["pass"] else capacity_pf

    # 2. batching server (greedy continuous batching: the previous
    # bucket's service time is the collect window)
    xb = ServerProc(os.path.join(args.out, "server_xbatch"),
                    batch=bucket, timeout_ms=args.xbatch_timeout_ms,
                    soak_s=600.0, profile=False)
    try:
        if not xb.wait_ready(probe_payload):
            print(json.dumps({"metric": "soak_xbatch", "pass": False,
                              "status": "infra_dead",
                              "vs_baseline": None,
                              "reason": "batching server never came up "
                                        "(see server.log)"}),
                  flush=True)
            return 2
        diagnosis = diagnose_endpoint("127.0.0.1", xb.port, timeout=5.0)
        if not diagnosis["ok"]:
            print(json.dumps({"metric": "soak_xbatch", "pass": False,
                              "status": "infra_dead",
                              "vs_baseline": None,
                              "diagnosis": diagnosis}), flush=True)
            return 2
        # warm every padded-bucket executable the soak can hit (fills
        # quantized to pow2/multiples-of-8, capped at the bucket)
        probe_conc = min(32, 2 * bucket)
        measure_capacity("127.0.0.1", xb.port, seconds=6.0,
                         payload=probe_payload, concurrency=probe_conc)
        capacity_xb = measure_capacity("127.0.0.1", xb.port,
                                       seconds=4.0,
                                       payload=probe_payload,
                                       concurrency=probe_conc)

        # 3. the soak: offer 4x the per-frame server's held-latency
        # goodput (4.4x for loadgen-jitter margin on the >=4.0 check).
        # Cap at 85% of measured capacity: past the knee an open-loop
        # soak measures queueing collapse, not the server.
        offered = 4.4 * baseline_rps
        if offered > 0.85 * capacity_xb:
            print(json.dumps({
                "note": "offered rate capped at 85% of measured "
                        "batching capacity; the 4x floor may not be "
                        "reachable on this host",
                "uncapped_rps": round(offered, 1),
                "capacity_xbatch_rps": round(capacity_xb, 1)}),
                flush=True)
            offered = 0.85 * capacity_xb
        rate = offered / clients
        evaluator = Evaluator(spec)
        monitor = SLOMonitor(evaluator)
        gen = LoadGenerator(
            "127.0.0.1", xb.port, clients=clients, rate_hz=rate,
            duration_s=duration, schedule=args.schedule, seed=args.seed,
            timeout=max(args.timeout, 5.0), payload=probe_payload)

        monitor.start()
        try:
            summary = gen.run()
        finally:
            monitor.stop(final_tick=True)
        final = xb.scrape()
        batched = int(xb.metric(final, "nns_xbatch_batched_total"))
        solo = int(xb.metric(final, "nns_xbatch_solo_total"))
        xb_frames = int(xb.metric(final, "nns_xbatch_frames_total"))
        pool_pending = int(xb.metric(final, "nns_pool_pending_slabs"))
    finally:
        xb.stop()

    # 4. attribution evidence: a SHORT traced pass on a fresh batching
    # server at the same offered rate (the bench.py precedent —
    # headline numbers stay untraced because full span tracing roughly
    # halves serving-row throughput on a 2-core CPU host, an observer
    # effect that would corrupt the very rps/latency being gated; the
    # blame SHAPE — which states dominate — survives the tax)
    attr_s = min(25.0, duration)
    xt = ServerProc(os.path.join(args.out, "server_xbatch_traced"),
                    batch=bucket, timeout_ms=args.xbatch_timeout_ms,
                    soak_s=300.0, profile=True)
    try:
        if not xt.wait_ready(probe_payload):
            print(json.dumps({"metric": "soak_xbatch", "pass": False,
                              "status": "infra_dead",
                              "vs_baseline": None,
                              "reason": "traced attribution server "
                                        "never came up"}), flush=True)
            return 2
        measure_capacity("127.0.0.1", xt.port, seconds=4.0,
                         payload=probe_payload, concurrency=probe_conc)
        # 0.8x the headline rate: the traced instance serves ~30%
        # slower (the observer tax), so the full rate would saturate
        # IT and the blame table would show queueing collapse instead
        # of the served operating point's state shape
        LoadGenerator(
            "127.0.0.1", xt.port, clients=clients, rate_hz=0.8 * rate,
            duration_s=attr_s, schedule=args.schedule, seed=args.seed,
            timeout=max(args.timeout, 5.0), payload=probe_payload).run()
    finally:
        xt.stop()
    profile = xt.profile()
    blame = (profile.get("profile") or {}).get("blame") \
        or profile.get("blame") or {}
    states = blame.get("states") or {}
    attribution = {}
    if blame.get("frames"):
        attribution = {
            "frames": blame["frames"], "e2e_us": blame.get("e2e_us"),
            "top": blame.get("top"),
            "states": {s: row["pct"] for s, row in states.items()},
            "attributed_pct": (blame.get("conservation") or {}).get(
                "attributed_pct"),
            "note": f"{attr_s:.0f}s traced pass at 0.8x the soak's "
                    "offered rate on its own server instance (the "
                    "traced instance serves ~30% slower — observer "
                    "tax — so the full rate would saturate it); "
                    "headline rps/latency come from the untraced "
                    "soak (see PERFORMANCE.md)"}
    admission_pct = attribution.get("states", {}).get(
        "admission-wait", 0.0)

    ok_rps = summary["ok"] / max(1e-9, summary["duration_s"])
    verdict = evaluator.verdict()
    checks = {
        "rps_4x_perframe": ok_rps >= 4.0 * baseline_rps,
        # baseline honesty, not baseline health: the per-frame server
        # FAILING its SLO even at the stepped-down rates is the r08
        # finding the batching exists to fix, so it must not fail the
        # acceptance — but then the bar must have used its RAW
        # closed-loop capacity (which is strictly higher than any
        # held-SLO goodput: the gate never profits from a failed
        # baseline run)
        "baseline_latency_honest": bool(pf_verdict["pass"])
        or baseline_rps >= capacity_pf,
        "latency_held": bool(verdict["pass"]),
        "admission_wait_reduced":
            bool(attribution) and admission_pct < R08_ADMISSION_WAIT_PCT,
        "buckets_formed": batched > 0 and xb_frames > batched,
        "no_leaked_slabs": pool_pending == 0,
    }
    mean_fill = xb_frames / batched if batched else 0.0
    verdict.update({
        "metric": "soak_xbatch", "status": "live",
        "pass": all(checks.values()),
        "verdict": "PASS" if all(checks.values()) else "FAIL",
        "loadgen": summary,
        "config": {
            "server": mlp_server_line(0, bucket,
                                      args.xbatch_timeout_ms),
            "note": "server runs OUT OF PROCESS via launch.py --soak "
                    "--profile --metrics-port (ROADMAP item 5: the "
                    "in-process demo's GIL contention suppressed the "
                    "very capacity under test); loadgen = PR 6 "
                    "open-loop soak, this process"},
        "xbatch": {
            "bucket": bucket,
            "batch_timeout_ms": args.xbatch_timeout_ms,
            "capacity_perframe_rps": round(capacity_pf, 1),
            "perframe_rps_at_slo": round(pf_rps, 1),
            "perframe_slo_verdict": pf_verdict["verdict"],
            "perframe_latency_us": pf_summary["latency_us"],
            "perframe_offered_frac": pf_frac,
            "baseline_rps": round(baseline_rps, 1),
            "capacity_xbatch_rps": round(capacity_xb, 1),
            "capacity_speedup": round(capacity_xb
                                      / max(1e-9, capacity_pf), 2),
            "offered_rps": round(offered, 1),
            "achieved_ok_rps": round(ok_rps, 1),
            "rps_vs_perframe_at_slo": round(
                ok_rps / max(1e-9, baseline_rps), 2),
            "buckets": {"batched": batched, "solo": solo,
                        "frames": xb_frames,
                        "mean_fill": round(mean_fill, 2)},
            "admission_wait_pct": admission_pct,
            "admission_wait_r08_pct": R08_ADMISSION_WAIT_PCT,
            "pool_pending_slabs": pool_pending,
            "checks": checks,
        },
    })
    if attribution:
        verdict["attribution"] = attribution
    # perf_diff-consumable rows: the regression gate's pinned input
    # (tests/test_xbatch.py) — if the batching win erodes, the
    # attribution delta names the stage
    rps_row = {"metric": "soak_xbatch_rps", "value": round(ok_rps, 1),
               "unit": "rps", "status": "live"}
    if attribution:
        rps_row["attribution"] = attribution
    verdict["rows"] = [
        rps_row,
        {"metric": "soak_perframe_capacity_rps",
         "value": round(capacity_pf, 1), "unit": "rps",
         "status": "live"},
        {"metric": "soak_perframe_rps_at_slo",
         "value": round(pf_rps, 1), "unit": "rps", "status": "live"},
        {"metric": "soak_xbatch_speedup_vs_perframe",
         "value": round(ok_rps / max(1e-9, baseline_rps), 2),
         "unit": "x_higher_better", "status": "live"},
        {"metric": "soak_xbatch_mean_fill", "value": round(mean_fill, 2),
         "unit": "frames_per_bucket", "status": "live"},
    ]
    with open(os.path.join(args.out, "verdict.json"), "w",
              encoding="utf-8") as fh:
        json.dump(verdict, fh, indent=2)
    line = {"metric": "soak_xbatch", "verdict": verdict["verdict"],
            "pass": verdict["pass"], "status": "live",
            "capacity_perframe_rps": round(capacity_pf, 1),
            "perframe_rps_at_slo": round(pf_rps, 1),
            "capacity_xbatch_rps": round(capacity_xb, 1),
            "offered_rps": round(offered, 1),
            "achieved_ok_rps": round(ok_rps, 1),
            "rps_vs_perframe_at_slo": round(
                ok_rps / max(1e-9, baseline_rps), 2),
            "mean_fill": round(mean_fill, 2),
            "admission_wait_pct": admission_pct,
            "latency_us": summary["latency_us"],
            "errors": summary["errors"],
            "checks": checks,
            "artifact": os.path.join(args.out, "verdict.json")}
    print(json.dumps(line), flush=True)
    return 0 if verdict["pass"] else 1



LLM_SERVER_ID = 95

#: the --llm soak's decoder sizing (registry custom= grammar,
#: models/streamformer_lm.config_from_custom — the ISSUE 15 satellite:
#: the soak server sizes a realistically heavy decoder from config
#: alone).  4 layers x d256/mlp1024 with a 512 vocab head: sequential
#: decode is a ~5 ms GEMV chain on the 2-core CPU host, so the batched
#: step's GEMM + single-dispatch economics are what the 2x gate
#: measures.  max_seq 512 bounds one slot's cache at
#: 4x512x8x32x4Bx2 = 2.1 MB; 12 slots + scratch = ~27 MB, FIXED.
LLM_CUSTOM = ("vocab:512,dim:256,heads:8,head_dim:32,mlp:1024,"
              "layers:4,max_seq:512,dtype:float32")
LLM_REQ_CAP = 96      # request frame length: header 3 + prompt <= 93
LLM_CAPS = (f"other/tensors,format=static,num_tensors=1,"
            f"dimensions={LLM_REQ_CAP},types=int32,framerate=0/1")


def llm_server_line(slots: int, batch: int,
                    sid: int = LLM_SERVER_ID) -> str:
    return (f"tensor_query_serversrc name=qsrc id={sid} port=0 "
            f"caps={LLM_CAPS} ! "
            f"tensor_llm name=llm custom={LLM_CUSTOM} seed=0 "
            f"slots={slots} batch={batch} id={sid} "
            f"max-new-tokens=96 ! "
            f"tensor_query_serversink id={sid}")


def _token_hist_quantiles(delta, family):
    """Per-class p50/p99 of one server-side token-latency histogram
    family (``nns_llm_ttft_us`` / ``nns_llm_itl_us``) from a
    ``snapshot_state`` window delta — the same bucket math the SLO
    evaluator uses, so the summary and the gate cannot disagree."""
    from nnstreamer_tpu.obs.metrics import quantile_from_counts

    per_class = {}
    for key, st in delta.items():
        if st.get("kind") != "histogram" \
                or key.partition("{")[0] != family:
            continue
        m = re.search(r'class="([^"]*)"', key)
        cls = m.group(1) if m else "default"
        cur = per_class.setdefault(cls, [0, None])
        cur[0] += int(st["count"])
        if cur[1] is None:
            cur[1] = list(st["counts"])
        else:
            for i, c in enumerate(st["counts"]):
                cur[1][i] += c
    out = {}
    for cls, (count, counts) in sorted(per_class.items()):
        if count and counts:
            out[cls] = {
                "count": count,
                "p50_us": round(quantile_from_counts(counts, 0.50), 1),
                "p99_us": round(quantile_from_counts(counts, 0.99), 1)}
    return out


def _token_latency_block(llm, delta):
    """The ``token_latency`` verdict block (ISSUE 20): per-class
    TTFT/ITL distributions (sheds/rejects excluded by construction —
    they only reach the terminal-cause counters), decode-plane blame
    shares (PhaseClock fold: sum to 100%% of decode-thread wall time
    by identity), terminal-cause counts, and per-session conservation
    evidence from the completed-record ring."""
    from nnstreamer_tpu.llm import tokenobs as _to

    tobs = getattr(llm, "_tok_obs", None)
    blame = tobs.blame_report() if tobs is not None else {}
    recs = tobs.records() if tobs is not None else []
    causes = {}
    for key, st in delta.items():
        if st.get("kind") != "counter" \
                or key.partition("{")[0] != _to.TERMINAL_TOTAL:
            continue
        m = re.search(r'cause="([^"]*)"', key)
        cause = m.group(1) if m else "?"
        v = int(st.get("value", 0))
        if v:
            causes[cause] = causes.get(cause, 0) + v
    conserved = [r["blame_conserved_pct"] for r in recs
                 if r.get("wall_ms", 0.0) > 1.0]
    # windowed blame from the monotone nns_llm_blame_ns_total
    # counters' delta (the soak's own decode-thread time); the
    # lifetime fold (which includes the warmup's compile share) rides
    # along as evidence
    blame_win = {}
    for key, st in delta.items():
        if st.get("kind") != "counter" \
                or key.partition("{")[0] != _to.BLAME_NS_TOTAL:
            continue
        m = re.search(r'cause="([^"]*)"', key)
        cause = m.group(1) if m else "?"
        v = int(st.get("value", 0))
        if v:
            blame_win[cause] = blame_win.get(cause, 0) + v
    total_win = sum(blame_win.values())
    block = {
        "ttft_us": _token_hist_quantiles(delta, _to.TTFT_US),
        "itl_us": _token_hist_quantiles(delta, _to.ITL_US),
        "blame_shares_pct": (
            {c: round(100.0 * v / total_win, 3)
             for c, v in sorted(blame_win.items())}
            if total_win else blame.get("shares_pct", {})),
        "blame_window_ns": total_win,
        "blame_lifetime_shares_pct": blame.get("shares_pct", {}),
        "blame_conserved_pct": blame.get("conserved_pct"),
        "terminal_causes": causes,
        "sessions_recorded": len(recs),
        "session_sample": recs[-3:],
    }
    if conserved:
        block["session_blame_conserved_pct"] = {
            "min": round(min(conserved), 3),
            "mean": round(sum(conserved) / len(conserved), 3),
            "max": round(max(conserved), 3), "n": len(conserved)}
    return block


def _llm_slo_monitor(duration_s, ttft_us=5_000_000.0,
                     itl_us=1_000_000.0):
    """Token-latency SLO monitor over the SERVER-side families: the
    ``ttft``/``itl`` objective kinds with ``metric`` overrides pointing
    at ``nns_llm_ttft_us``/``nns_llm_itl_us`` (the element's own
    observations — the soak's clients are in-process threads, so the
    wire-side loadgen families are not in play here).  Windows scale
    with the soak the way demo_spec's do; thresholds are CPU-host
    budgets (first token within 5 s by default — the paged soak
    passes 10 s because its cold half saturates admission by design —
    every inter-token gap within 1 s, >= 90%% of each): generous
    against a healthy run, decisively breached by a stalled decode
    plane."""
    from nnstreamer_tpu.llm.tokenobs import ITL_US, TTFT_US
    from nnstreamer_tpu.slo.evaluator import Evaluator, SLOMonitor
    from nnstreamer_tpu.slo.spec import Objective, SLOSpec

    fast = max(2.0, duration_s / 6.0)
    spec = SLOSpec(
        name="llm-token-latency",
        window_fast_s=fast, window_slow_s=fast * 10.0,
        burn_threshold=2.0, tick_s=max(0.25, fast / 10.0),
        objectives=(
            Objective("ttft", "ttft", target=0.90,
                      threshold_us=ttft_us, metric=TTFT_US),
            Objective("itl", "itl", target=0.90,
                      threshold_us=itl_us, metric=ITL_US),
        ))
    return SLOMonitor(Evaluator(spec))


def run_llm(args, ap) -> int:
    """Token-streaming LLM serving acceptance soak (ISSUE 15): a
    multi-client soak against the ``tensor_llm`` continuous-batching
    serving pipeline, clients with wildly different prompt/output
    lengths joining and leaving continuously.  Gates:

    - **zero client errors** and **exact per-client token order**
      (TokenStreamClient raises on any pts gap — an order violation IS
      an error);
    - **explicit overload**: every refused session is a counted
      T_SHED with retry-after (clients honor it and retry), server and
      client shed counts agree;
    - **bounded cache memory**: the pooled cache's device bytes are
      IDENTICAL before and after the soak (static by construction) and
      zero pooled wire slabs leak;
    - **continuous batching pays**: aggregate soak tokens/s >= 2x the
      one-session-at-a-time baseline measured on the same server;
    - **consistency under batching**: a probe prompt replayed
      mid-soak (different bucket compositions) yields byte-identical
      token streams;
    - **conserved attribution**: the decode thread's prefill/decode/
      idle wall-time attribution sums to 100% exactly (PhaseClock
      identity), recorded in the verdict the way PR 8 profiles are.
    """
    import threading as _threading
    import time as _time

    import numpy as np

    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.llm.client import TokenStreamClient
    from nnstreamer_tpu.query.overload import ShedError
    from nnstreamer_tpu.query.server import get_server, shutdown_server
    from nnstreamer_tpu.tensor.buffer import default_pool

    os.makedirs(args.out, exist_ok=True)
    slots, batch = args.llm_slots, args.llm_batch
    clients = args.clients or 16
    duration = args.duration
    pipeline = parse_launch(llm_server_line(slots, batch))
    pipeline.play()
    port = pipeline.get("qsrc").bound_port
    llm = pipeline.get("llm")
    cache_bytes_start = llm.pool.cache_bytes()

    probe_prompt = np.arange(7, dtype=np.int32) % 512
    probe_new = 24

    def one_session(cli, rng, counters):
        plen = int(rng.integers(4, 64))
        n_new = int(rng.integers(8, 72))
        prompt = rng.integers(0, 512, plen).astype(np.int32)
        while True:
            try:
                toks = cli.generate(prompt, n_new,
                                    frame_len=LLM_REQ_CAP)
                counters["tokens"] += len(toks)
                counters["sessions"] += 1
                return
            except ShedError as exc:
                counters["sheds"] += 1
                _time.sleep(min(exc.retry_after_s, 1.0))

    # 1. solo baseline: ONE client, sessions back to back — the
    # one-session-at-a-time decode rate the batched soak must beat 2x
    solo = {"tokens": 0, "sessions": 0, "sheds": 0}
    cli = TokenStreamClient("127.0.0.1", port, timeout=60.0).connect()
    rng = np.random.default_rng(args.seed)
    one_session(cli, rng, solo)            # warm (prefill compiles)
    solo = {"tokens": 0, "sessions": 0, "sheds": 0}
    t0 = _time.monotonic()
    while _time.monotonic() - t0 < max(8.0, duration / 5):
        one_session(cli, rng, solo)
    solo_s = _time.monotonic() - t0
    cli.close()
    solo_tok_s = solo["tokens"] / solo_s

    # token-latency plane (ISSUE 20): baseline the server-side
    # nns_llm_* families AFTER the solo warmup so the soak's block is
    # the soak's distribution, and gate the run with the ttft/itl SLO
    # kinds over the same histograms
    from nnstreamer_tpu.obs.metrics import REGISTRY as _REG
    from nnstreamer_tpu.obs.metrics import state_delta as _state_delta

    if llm._tok_obs is not None:
        # flush pre-soak blame (warmup compile) into the counters so
        # the baseline snapshot absorbs it — the windowed blame shares
        # below must describe the SOAK, not the element's lifetime
        llm._tok_obs.sync_blame_counters()
    tok0 = _REG.snapshot_state(prefix="nns_llm_")
    slo_monitor = _llm_slo_monitor(duration).start()

    # 2. the soak: clients join and leave continuously (half reconnect
    # per session — connection churn exercises disconnect pruning on
    # top of clean completions)
    stop = _threading.Event()
    stats = []
    errors = []

    def client_loop(i):
        counters = {"tokens": 0, "sessions": 0, "sheds": 0}
        stats.append(counters)
        rng = np.random.default_rng(1000 + args.seed + i)
        reconnect = i % 2 == 0
        cli = None
        try:
            cli = TokenStreamClient("127.0.0.1", port,
                                    timeout=120.0).connect()
            while not stop.is_set():
                one_session(cli, rng, counters)
                if reconnect and not stop.is_set():
                    cli.close()
                    _time.sleep(float(rng.uniform(0, 0.05)))
                    cli = TokenStreamClient(
                        "127.0.0.1", port, timeout=120.0).connect()
        except Exception as exc:  # noqa: BLE001 — the zero-errors gate
            if not stop.is_set():
                errors.append(f"client {i}: {exc!r}")
        finally:
            if cli is not None:
                cli.close()

    def abandoner_loop(i):
        """Mid-stream disconnector: starts a long stream, reads a few
        tokens, vanishes.  The element's disconnect pruner must
        reclaim the slot (evicted counter) with zero leaked slabs —
        abandonment is designed behavior, never an error."""
        counters = {"tokens": 0, "sessions": 0, "sheds": 0}
        stats.append(counters)
        rng = np.random.default_rng(5000 + args.seed + i)
        while not stop.is_set():
            cli = None
            try:
                cli = TokenStreamClient("127.0.0.1", port,
                                        timeout=120.0).connect()
                prompt = rng.integers(0, 512, 8).astype(np.int32)
                stream = cli.stream(prompt, 80, frame_len=LLM_REQ_CAP)
                for _ in range(int(rng.integers(2, 6))):
                    next(stream)
            except ShedError:
                counters["sheds"] += 1
            except StopIteration:
                pass
            except Exception as exc:  # noqa: BLE001
                if not stop.is_set():
                    errors.append(f"abandoner {i}: {exc!r}")
            finally:
                if cli is not None:
                    cli.close()          # vanish mid-stream
            stop.wait(float(rng.uniform(0.3, 0.8)))

    def probe_loop():
        """Mid-soak consistency probe: the SAME prompt replayed under
        different bucket compositions must stream identical tokens."""
        runs = []
        counters = {"tokens": 0, "sessions": 0, "sheds": 0}
        stats.append(counters)
        try:
            cli = TokenStreamClient("127.0.0.1", port,
                                    timeout=120.0).connect()
            for _ in range(2):
                _time.sleep(duration / 4)
                while True:
                    try:
                        runs.append(cli.generate(
                            probe_prompt, probe_new,
                            frame_len=LLM_REQ_CAP))
                        break
                    except ShedError as exc:
                        counters["sheds"] += 1
                        _time.sleep(min(exc.retry_after_s, 1.0))
            cli.close()
        except Exception as exc:  # noqa: BLE001
            errors.append(f"probe: {exc!r}")
        probe_results.extend(runs)

    probe_results = []
    threads = [_threading.Thread(target=client_loop, args=(i,),
                                 daemon=True) for i in range(clients)]
    threads.extend(_threading.Thread(target=abandoner_loop, args=(i,),
                                     daemon=True) for i in range(2))
    threads.append(_threading.Thread(target=probe_loop, daemon=True))
    t0 = _time.monotonic()
    for t in threads:
        t.start()
    stop.wait(duration)
    stop.set()
    for t in threads:
        t.join(timeout=180)
    soak_s = _time.monotonic() - t0

    srv = get_server(LLM_SERVER_ID)
    deadline = _time.monotonic() + 30
    while srv._inflight > 0 and _time.monotonic() < deadline:
        _time.sleep(0.1)
    slo_monitor.stop()
    slo_verdict = slo_monitor.evaluator.verdict()
    if llm._tok_obs is not None:
        llm._tok_obs.sync_blame_counters()
    tok_delta = _state_delta(_REG.snapshot_state(prefix="nns_llm_"),
                             tok0)
    token_latency = _token_latency_block(llm, tok_delta)
    engine_report = llm.engine.report()
    cache_bytes_end = llm.pool.cache_bytes()
    shed_server = llm.shed_total
    evicted = llm.evicted_total
    sessions_started = llm.sessions_total
    inflight_end = srv._inflight
    pipeline.stop()
    shutdown_server(LLM_SERVER_ID)
    import gc

    gc.collect()
    pool_pending = default_pool().stats["pending"]

    tokens = sum(c["tokens"] for c in stats)
    sessions = sum(c["sessions"] for c in stats)
    sheds_client = sum(c["sheds"] for c in stats)
    tok_s = tokens / soak_s
    phases = engine_report["phases"]
    checks = {
        "zero_errors": not errors,
        "exact_order": not any("order" in e for e in errors),
        "sheds_explicit": sheds_client == shed_server,
        "cache_bounded": (cache_bytes_end == cache_bytes_start
                          and pool_pending == 0),
        "batched_2x_solo": tok_s >= 2.0 * solo_tok_s,
        "consistency_under_batching": (
            len(probe_results) == 2
            and probe_results[0] == probe_results[1]),
        "attribution_conserved":
            abs(phases["conserved_pct"] - 100.0) < 0.1,
        "inflight_settled": inflight_end == 0,
        # the abandoner clients guarantee mid-stream disconnects
        # happened; the pruner must have reclaimed every one (final
        # live == 0 is implied by inflight_settled + pipeline.stop)
        "disconnects_reclaimed": evicted >= 1,
        # ISSUE 20 token-latency gates: the ttft/itl SLO objectives
        # never breached, and the per-session blame accumulators
        # reconcile with each session's own admit->terminal wall time
        # (the partition is an identity; the sub-ms slack is the
        # independent clock reads that stamp the window's edges)
        "token_slo_pass": slo_verdict["pass"],
        "session_blame_conserved": (
            "session_blame_conserved_pct" in token_latency
            and abs(token_latency["session_blame_conserved_pct"]
                    ["mean"] - 100.0) < 1.0),
    }
    attribution = {
        "states": dict(phases["states_pct"]),
        "conserved_pct": phases["conserved_pct"],
        "note": "DecodeEngine PhaseClock: every decode-thread "
                "nanosecond in exactly one of idle/admit/prefill/"
                "decode/egress — conservation is an identity "
                "(obs/attrib.py llm-prefill/llm-decode are the "
                "per-frame trace twins)"}
    verdict = {
        "metric": "soak_llm", "status": "live",
        "pass": all(checks.values()),
        "verdict": "PASS" if all(checks.values()) else "FAIL",
        "config": {"server": llm_server_line(slots, batch),
                   "clients": clients, "duration_s": round(soak_s, 1),
                   "note": "in-process serving pipeline + threaded "
                           "token-stream clients; prompt lengths "
                           "4..63, output lengths 8..71, half the "
                           "clients reconnect per session"},
        "llm": {
            "slots": slots, "batch": batch,
            "tokens": tokens, "sessions": sessions,
            "sessions_started_server": sessions_started,
            "tokens_per_s": round(tok_s, 1),
            "solo_tokens_per_s": round(solo_tok_s, 1),
            "speedup_vs_solo": round(tok_s / max(1e-9, solo_tok_s), 2),
            "mean_step_fill": engine_report["mean_fill"],
            "ewma_step_ms": engine_report["ewma_step_ms"],
            "compiles": engine_report["compiles"],
            "sheds_client": sheds_client, "sheds_server": shed_server,
            "evicted_sessions": evicted,
            "cache_bytes": cache_bytes_end,
            "pool_pending_slabs": pool_pending,
            "errors": errors[:10],
            "checks": checks,
        },
        "attribution": attribution,
        "token_latency": token_latency,
        "slo": slo_verdict,
    }
    tok_row = {"metric": "soak_llm_tokens_per_s",
               "value": round(tok_s, 1), "unit": "tokens_per_s",
               "status": "live", "attribution": attribution}
    verdict["rows"] = [
        tok_row,
        {"metric": "soak_llm_solo_tokens_per_s",
         "value": round(solo_tok_s, 1), "unit": "tokens_per_s",
         "status": "live"},
        {"metric": "soak_llm_speedup_vs_solo",
         "value": round(tok_s / max(1e-9, solo_tok_s), 2),
         "unit": "x_higher_better", "status": "live"},
        {"metric": "soak_llm_mean_step_fill",
         "value": engine_report["mean_fill"],
         "unit": "seqs_per_step", "status": "live"},
    ]
    ttft_p99 = max((v["p99_us"]
                    for v in token_latency["ttft_us"].values()),
                   default=0.0)
    itl_p99 = max((v["p99_us"]
                   for v in token_latency["itl_us"].values()),
                  default=0.0)
    verdict["rows"].extend([
        {"metric": "soak_llm_ttft_p99_us", "value": ttft_p99,
         "unit": "us", "status": "live"},
        {"metric": "soak_llm_itl_p99_us", "value": itl_p99,
         "unit": "us", "status": "live"},
    ])
    with open(os.path.join(args.out, "verdict.json"), "w",
              encoding="utf-8") as fh:
        json.dump(verdict, fh, indent=2)
    line = {"metric": "soak_llm", "verdict": verdict["verdict"],
            "pass": verdict["pass"],
            "tokens_per_s": round(tok_s, 1),
            "solo_tokens_per_s": round(solo_tok_s, 1),
            "speedup_vs_solo": round(tok_s / max(1e-9, solo_tok_s), 2),
            "mean_step_fill": engine_report["mean_fill"],
            "sessions": sessions, "sheds": sheds_client,
            "evicted": evicted, "errors": len(errors),
            "prefill_pct": phases["states_pct"].get("prefill"),
            "decode_pct": phases["states_pct"].get("decode"),
            "conserved_pct": phases["conserved_pct"],
            "ttft_p99_us": ttft_p99, "itl_p99_us": itl_p99,
            "token_slo": slo_verdict["verdict"],
            "checks": checks,
            "artifact": os.path.join(args.out, "verdict.json")}
    print(json.dumps(line), flush=True)
    return 0 if verdict["pass"] else 1


LLM_DENSE_REF_ID = 96


def llm_paged_server_line(slots: int, batch: int, pages: int,
                          page_size: int, chunk: int,
                          sid: int = LLM_SERVER_ID) -> str:
    return (f"tensor_query_serversrc name=qsrc id={sid} port=0 "
            f"caps={LLM_CAPS} ! "
            f"tensor_llm name=llm custom={LLM_CUSTOM} seed=0 "
            f"slots={slots} batch={batch} id={sid} "
            f"page-size={page_size} pages={pages} "
            f"prefill-chunk={chunk} prefix-cache=1 "
            f"max-new-tokens=96 ! "
            f"tensor_query_serversink id={sid}")


def run_llm_paged(args, ap) -> int:
    """Paged-KV serving acceptance soak (ISSUE 17): the short-chat mix
    against a ``tensor_llm`` server backed by the block-paged arena,
    sized to the SAME device bytes as a dense reference server.  Gates:

    - **memory-proportional residency**: peak concurrently-resident
      sessions on the paged server >= 2x the dense server's slot count
      at identical arena bytes (the whole point of paging);
    - **byte-identity**: a probe prompt streamed on the DENSE server is
      the reference; the paged server replays it mid-soak (different
      bucket compositions, chunked prefill interleave) and idle — every
      stream must be token-identical;
    - **prefix caching pays**: phase A runs UNIQUE prompts (cold),
      phase B the same mix behind one shared 64-token system prompt —
      phase B must show prefix-cache hits and a busy-time prefill share
      measurably below phase A's (only the per-client tail computes);
    - **chunked prefill interleaves**: the PhaseClock's
      ``llm-prefill-chunk`` share is nonzero (prompts advance in
      bounded chunks between decode steps, never as one stall);
    - **bounded memory**: arena bytes identical before/after, zero page
      / refcount / reservation leaks after drain, zero leaked slabs;
    - **zero steady-state compiles** after the paged warmup grid;
    - **zero client errors** and **exact per-client order**, as ever.
    """
    import threading as _threading
    import time as _time

    import numpy as np

    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.llm.client import TokenStreamClient
    from nnstreamer_tpu.query.overload import ShedError
    from nnstreamer_tpu.query.server import get_server, shutdown_server
    from nnstreamer_tpu.tensor.buffer import default_pool

    os.makedirs(args.out, exist_ok=True)
    batch = args.llm_batch
    dense_slots = max(3, args.llm_slots // 2)
    paged_slots = 4 * dense_slots
    page_size = 8
    table_max = 512 // page_size          # LLM_CUSTOM max_seq
    pages = (dense_slots + 1) * table_max - 1   # == dense arena bytes
    # chunk 8 = one page per chunk: a cold 84-88-token prompt costs 11
    # chunks, a warm one (10 shared pages hit, a <=8-token tail) exactly
    # 1 — the contrast the prefill-share gate measures
    chunk = 8
    clients = args.clients or paged_slots + 4
    duration = args.duration
    probe_prompt = np.arange(7, dtype=np.int32) % 512
    probe_new = 24
    sys_prompt = (np.arange(80, dtype=np.int32) * 7 + 11) % 512

    def _probe(cli, counters):
        while True:
            try:
                return cli.generate(probe_prompt, probe_new,
                                    frame_len=LLM_REQ_CAP)
            except ShedError as exc:
                counters["sheds"] += 1
                _time.sleep(min(exc.retry_after_s, 1.0))

    # 1. dense reference server: the probe's byte-identity baseline and
    # the arena-bytes / residency baseline (a dense pool can never hold
    # more than `dense_slots` sessions — that IS the waste)
    dense_batch = min(batch, dense_slots)
    dense = parse_launch(llm_server_line(dense_slots, dense_batch,
                                         sid=LLM_DENSE_REF_ID))
    dense.play()
    dense_port = dense.get("qsrc").bound_port
    dense_bytes = dense.get("llm").pool.cache_bytes()
    ref_counters = {"sheds": 0}
    cli = TokenStreamClient("127.0.0.1", dense_port,
                            timeout=120.0).connect()
    probe_ref = _probe(cli, ref_counters)
    probe_ref2 = _probe(cli, ref_counters)
    cli.close()
    dense.stop()
    shutdown_server(LLM_DENSE_REF_ID)

    # 2. the paged server, at the DENSE server's arena bytes
    pipeline = parse_launch(llm_paged_server_line(
        paged_slots, batch, pages, page_size, chunk))
    pipeline.play()
    port = pipeline.get("qsrc").bound_port
    llm = pipeline.get("llm")
    pool = llm.pool
    cache_bytes_start = pool.cache_bytes()
    compiles_warm = llm.engine.compiles   # warmup grid is complete here

    # token-latency plane (ISSUE 20): baseline the server-side
    # nns_llm_* families (the dense reference ran first — diffing
    # excludes it) and gate with the ttft/itl SLO kinds; a second
    # snapshot at the cold->warm flip splits the TTFT distribution so
    # the warm-prefix win is measured INSIDE one run
    from nnstreamer_tpu.obs.metrics import REGISTRY as _REG
    from nnstreamer_tpu.obs.metrics import state_delta as _state_delta

    if llm._tok_obs is not None:
        # flush pre-soak blame (the paged plan's warmup compile) into
        # the counters so the baseline absorbs it — otherwise the
        # first lazy sync lands the whole warmup inside the window
        llm._tok_obs.sync_blame_counters()
    tok0 = _REG.snapshot_state(prefix="nns_llm_")
    # the cold half DELIBERATELY saturates admission: every client
    # replays an ~85-token prompt as 11 prefill chunks, so first
    # tokens queue for seconds by design.  10 s is the budget that
    # separates "saturated but flowing" from a stalled decode plane
    # (a head-of-line stall parks first tokens for the whole phase).
    slo_monitor = _llm_slo_monitor(duration,
                                   ttft_us=10_000_000.0).start()

    stop = _threading.Event()
    phase = {"mode": "cold"}
    stats = []
    errors = []
    peak = {"live": 0}

    def sampler_loop():
        while not stop.is_set():
            peak["live"] = max(peak["live"], pool.live)
            stop.wait(0.03)

    def client_loop(i):
        counters = {"tokens": 0, "sessions": 0, "sheds": 0}
        stats.append(counters)
        rng = np.random.default_rng(2000 + args.seed + i)
        try:
            cli = TokenStreamClient("127.0.0.1", port,
                                    timeout=120.0).connect()
            while not stop.is_set():
                if phase["mode"] == "cold":
                    # unique prompt, same length as the warm mix: the
                    # prefill WORK matches, only the sharing differs
                    prompt = rng.integers(
                        0, 512, 80 + int(rng.integers(4, 9))
                    ).astype(np.int32)
                else:
                    tail = rng.integers(
                        0, 512, int(rng.integers(4, 9))).astype(np.int32)
                    prompt = np.concatenate([sys_prompt, tail])
                # 24-41 output tokens: long enough that a warm session
                # (one tail chunk) is decode-dominated while a cold one
                # (11 chunks) stays prefill-bound — the share contrast
                # the warm gate measures
                n_new = int(rng.integers(24, 42))
                try:
                    toks = cli.generate(prompt, n_new,
                                        frame_len=LLM_REQ_CAP)
                    counters["tokens"] += len(toks)
                    counters["sessions"] += 1
                    # a short think time keeps demand rate-limited, not
                    # saturation-limited: cheaper prefill then SHOWS as
                    # a smaller busy share instead of more admissions
                    stop.wait(0.04)
                except ShedError as exc:
                    counters["sheds"] += 1
                    _time.sleep(min(exc.retry_after_s, 1.0))
            cli.close()
        except Exception as exc:  # noqa: BLE001 — the zero-errors gate
            if not stop.is_set():
                errors.append(f"client {i}: {exc!r}")

    probe_paged = []

    def probe_loop():
        counters = {"sheds": 0}
        try:
            cli = TokenStreamClient("127.0.0.1", port,
                                    timeout=120.0).connect()
            for _ in range(2):
                _time.sleep(duration / 4)
                probe_paged.append(_probe(cli, counters))
            cli.close()
        except Exception as exc:  # noqa: BLE001
            errors.append(f"probe: {exc!r}")

    threads = [_threading.Thread(target=client_loop, args=(i,),
                                 daemon=True) for i in range(clients)]
    threads.append(_threading.Thread(target=probe_loop, daemon=True))
    threads.append(_threading.Thread(target=sampler_loop, daemon=True))
    t0 = _time.monotonic()
    for t in threads:
        t.start()

    def _phase_snap():
        rep = llm.engine.phases.report()
        return (dict(rep["states_s"]),
                {"hits": pool.prefix_hits,
                 "reused": pool.prefix_tokens_reused})

    cold0, pfx0 = _phase_snap()
    stop.wait(duration / 2)
    # seed the warm registry BEFORE the cohort flips: prefix pages
    # register only as a prefill ADVANCES past them, so 24 sessions
    # admitting the shared prompt simultaneously would all miss (the
    # cold-identical race) — one completed session first, and every
    # warm admission after it hits
    seed_cli = TokenStreamClient("127.0.0.1", port,
                                 timeout=120.0).connect()
    while True:
        try:
            seed_cli.generate(
                np.concatenate([sys_prompt,
                                np.asarray([1, 2, 3], np.int32)]),
                8, frame_len=LLM_REQ_CAP)
            break
        except ShedError as exc:
            _time.sleep(min(exc.retry_after_s, 1.0))
    seed_cli.close()
    cold1, pfx1 = _phase_snap()
    if llm._tok_obs is not None:
        llm._tok_obs.sync_blame_counters()
    tok_flip = _REG.snapshot_state(prefix="nns_llm_")
    phase["mode"] = "warm"
    stop.wait(duration / 2)
    warm1, pfx2 = _phase_snap()
    stop.set()
    for t in threads:
        t.join(timeout=180)
    soak_s = _time.monotonic() - t0
    slo_monitor.stop()
    slo_verdict = slo_monitor.evaluator.verdict()
    if llm._tok_obs is not None:
        llm._tok_obs.sync_blame_counters()
    tok_end = _REG.snapshot_state(prefix="nns_llm_")

    def _busy_prefill_share(a, b):
        d = {k: b[k] - a[k] for k in b}
        busy = sum(v for k, v in d.items() if k != "idle")
        pre = d.get("prefill", 0.0) + d.get("llm-prefill-chunk", 0.0)
        return pre / max(1e-9, busy), d

    cold_share, cold_states = _busy_prefill_share(cold0, cold1)
    warm_share, warm_states = _busy_prefill_share(cold1, warm1)
    hits_cold = pfx1["hits"] - pfx0["hits"]
    hits_warm = pfx2["hits"] - pfx1["hits"]
    reused_warm = pfx2["reused"] - pfx1["reused"]

    from nnstreamer_tpu.llm.tokenobs import TTFT_US as _TTFT

    token_latency = _token_latency_block(
        llm, _state_delta(tok_end, tok0))
    ttft_cold = _token_hist_quantiles(_state_delta(tok_flip, tok0),
                                      _TTFT)
    ttft_warm = _token_hist_quantiles(_state_delta(tok_end, tok_flip),
                                      _TTFT)

    def _agg_p50(block):
        return max((v["p50_us"] for v in block.values()), default=0.0)

    ttft_cold_p50 = _agg_p50(ttft_cold)
    ttft_warm_p50 = _agg_p50(ttft_warm)
    token_latency["ttft_cold_phase_us"] = ttft_cold
    token_latency["ttft_warm_phase_us"] = ttft_warm
    token_latency["ttft_warm_vs_cold_p50"] = round(
        ttft_warm_p50 / max(1e-9, ttft_cold_p50), 3)

    srv = get_server(LLM_SERVER_ID)
    deadline = _time.monotonic() + 30
    while srv._inflight > 0 and _time.monotonic() < deadline:
        _time.sleep(0.1)
    # idle replay: bucket composition nothing like mid-soak
    final_counters = {"sheds": 0}
    cli = TokenStreamClient("127.0.0.1", port, timeout=120.0).connect()
    probe_paged.append(_probe(cli, final_counters))
    cli.close()
    deadline = _time.monotonic() + 30
    while srv._inflight > 0 and _time.monotonic() < deadline:
        _time.sleep(0.1)
    engine_report = llm.engine.report()
    compiles_end = llm.engine.compiles
    cache_bytes_end = pool.cache_bytes()
    leaks = pool.check_leaks()
    free_end = pool.free_pages
    inflight_end = srv._inflight
    evicted = llm.evicted_total
    pipeline.stop()
    shutdown_server(LLM_SERVER_ID)
    import gc

    gc.collect()
    pool_pending = default_pool().stats["pending"]

    tokens = sum(c["tokens"] for c in stats)
    sessions = sum(c["sessions"] for c in stats)
    sheds_client = sum(c["sheds"] for c in stats)
    tok_s = tokens / soak_s
    phases = engine_report["phases"]
    probes_all = [probe_ref, probe_ref2] + probe_paged
    checks = {
        "zero_errors": not errors,
        "exact_order": not any("order" in e for e in errors),
        "arena_bytes_equal_dense": cache_bytes_start == dense_bytes,
        "arena_bytes_fixed": cache_bytes_end == cache_bytes_start,
        "residency_2x_dense": peak["live"] >= 2 * dense_slots,
        "replay_identical_to_dense": (
            len(probe_paged) == 3
            and all(p == probe_ref for p in probes_all)),
        "prefix_hits_warm": hits_warm > 0 and reused_warm > 0,
        "prefill_share_drops_warm": warm_share <= 0.75 * cold_share,
        "chunk_share_present":
            phases["states_s"].get("llm-prefill-chunk", 0.0) > 0.0,
        "zero_steady_compiles": compiles_end == compiles_warm,
        "zero_page_leaks": not leaks and free_end == pages,
        "slabs_settled": pool_pending == 0 and inflight_end == 0,
        "attribution_conserved":
            abs(phases["conserved_pct"] - 100.0) < 0.1,
        # ISSUE 20 token-latency gates: the ttft/itl SLO objectives
        # never breached; per-session blame reconciles with each
        # session's own wall window; and a warm-prefix first token is
        # measurably cheaper than a cold one INSIDE this run (a warm
        # 4-8 token tail prefills in 1 chunk vs 11 cold — p50 must
        # show it through the interleave)
        "token_slo_pass": slo_verdict["pass"],
        "session_blame_conserved": (
            "session_blame_conserved_pct" in token_latency
            and abs(token_latency["session_blame_conserved_pct"]
                    ["mean"] - 100.0) < 1.0),
        "ttft_warm_below_cold": (
            ttft_warm_p50 > 0.0
            and ttft_warm_p50 <= 0.9 * ttft_cold_p50),
    }
    verdict = {
        "metric": "soak_llm_paged", "status": "live",
        "pass": all(checks.values()),
        "verdict": "PASS" if all(checks.values()) else "FAIL",
        "config": {
            "server": llm_paged_server_line(paged_slots, batch, pages,
                                            page_size, chunk),
            "dense_reference": llm_server_line(dense_slots, dense_batch,
                                               sid=LLM_DENSE_REF_ID),
            "clients": clients, "duration_s": round(soak_s, 1),
            "note": "short-chat mix (84-88 token prompts, 24-41 new, "
                    "40 ms think time); phase A unique prompts (cold), "
                    "phase B one shared 80-token system prompt + "
                    "unique tails (warm, registry seeded at the flip); "
                    "paged arena sized byte-identical to the dense "
                    "reference"},
        "llm_paged": {
            "page_size": page_size, "pages": pages,
            "paged_slots": paged_slots, "dense_slots": dense_slots,
            "batch": batch,
            "tokens": tokens, "sessions": sessions,
            "tokens_per_s": round(tok_s, 1),
            "arena_bytes": cache_bytes_end,
            "dense_arena_bytes": dense_bytes,
            "peak_resident": peak["live"],
            "residency_ratio_vs_dense": round(
                peak["live"] / max(1, dense_slots), 2),
            "prefix_hits_cold": hits_cold,
            "prefix_hits_warm": hits_warm,
            "prefix_tokens_reused_warm": reused_warm,
            "cold_busy_prefill_share": round(cold_share, 4),
            "warm_busy_prefill_share": round(warm_share, 4),
            "warm_vs_cold_prefill": round(
                warm_share / max(1e-9, cold_share), 3),
            "cold_states_s": {k: round(v, 3)
                              for k, v in cold_states.items()},
            "warm_states_s": {k: round(v, 3)
                              for k, v in warm_states.items()},
            "prefill_chunks": engine_report.get("prefill_chunks"),
            "compiles_after_warmup": compiles_warm,
            "steady_state_compiles": compiles_end - compiles_warm,
            "sheds_client": sheds_client,
            "evicted_sessions": evicted,
            "page_leaks": leaks,
            "pool_pending_slabs": pool_pending,
            "paged_stats": engine_report.get("paged"),
            "errors": errors[:10],
            "checks": checks,
        },
        "token_latency": token_latency,
        "slo": slo_verdict,
    }
    attribution = {
        "states": dict(phases["states_pct"]),
        "conserved_pct": phases["conserved_pct"],
        "note": "DecodeEngine PhaseClock with the llm-prefill-chunk "
                "state: bounded prefill chunks interleaved between "
                "decode steps — a ballooning chunk share IS the blame "
                "signature of a chunked-prefill regression"}
    verdict["attribution"] = attribution
    verdict["rows"] = [
        {"metric": "soak_llm_paged_tokens_per_s",
         "value": round(tok_s, 1), "unit": "tokens_per_s",
         "status": "live", "attribution": attribution},
        {"metric": "soak_llm_paged_residency_ratio",
         "value": round(peak["live"] / max(1, dense_slots), 2),
         "unit": "x_higher_better", "status": "live"},
        {"metric": "soak_llm_paged_prefix_hits_warm",
         "value": hits_warm, "unit": "sessions", "status": "live"},
        {"metric": "soak_llm_paged_warm_vs_cold_prefill_pct",
         "value": round(100.0 * warm_share / max(1e-9, cold_share), 1),
         "unit": "pct", "status": "live"},
    ]
    ttft_p99 = max((v["p99_us"]
                    for v in token_latency["ttft_us"].values()),
                   default=0.0)
    itl_p99 = max((v["p99_us"]
                   for v in token_latency["itl_us"].values()),
                  default=0.0)
    verdict["rows"].extend([
        {"metric": "soak_llm_paged_ttft_p99_us", "value": ttft_p99,
         "unit": "us", "status": "live"},
        {"metric": "soak_llm_paged_itl_p99_us", "value": itl_p99,
         "unit": "us", "status": "live"},
        {"metric": "soak_llm_paged_ttft_warm_vs_cold_pct",
         "value": round(100.0 * ttft_warm_p50
                        / max(1e-9, ttft_cold_p50), 1),
         "unit": "pct", "status": "live"},
    ])
    with open(os.path.join(args.out, "verdict.json"), "w",
              encoding="utf-8") as fh:
        json.dump(verdict, fh, indent=2)
    line = {"metric": "soak_llm_paged", "verdict": verdict["verdict"],
            "pass": verdict["pass"],
            "tokens_per_s": round(tok_s, 1),
            "peak_resident": peak["live"],
            "residency_ratio_vs_dense": round(
                peak["live"] / max(1, dense_slots), 2),
            "prefix_hits_warm": hits_warm,
            "warm_vs_cold_prefill": round(
                warm_share / max(1e-9, cold_share), 3),
            "steady_state_compiles": compiles_end - compiles_warm,
            "sessions": sessions, "errors": len(errors),
            "ttft_p99_us": ttft_p99, "itl_p99_us": itl_p99,
            "ttft_warm_vs_cold_p50":
                token_latency["ttft_warm_vs_cold_p50"],
            "token_slo": slo_verdict["verdict"],
            "checks": checks,
            "artifact": os.path.join(args.out, "verdict.json")}
    print(json.dumps(line), flush=True)
    return 0 if verdict["pass"] else 1


FEDERATE_SERVER_ID = 93
FLEET_SERVER_ID = 94

#: the fleet workers' launch template (fleet/pool.py launch_spawn_fn
#: fills {port}): the light demo serving pipeline, so the soak
#: exercises fleet mechanics — routing, kill/rebalance, autoscaling —
#: not model compile time
FLEET_WORKER_TEMPLATE = (
    f"tensor_query_serversrc name=qsrc id={FLEET_SERVER_ID} "
    "port={port} caps=" + DEMO_CAPS + " ! "
    "tensor_transform mode=arithmetic option=mul:2 ! "
    f"tensor_query_serversink id={FLEET_SERVER_ID}")


def run_fleet(args, ap) -> int:
    """Fleet acceptance soak (ROADMAP item 3, the ISSUE 14 gate): a
    REAL multi-process fleet — router in this process, >=3 launch.py
    workers federating into this process's collector — driven through
    three phases:

    1. **kill leg**: PR 6 open-loop load through the router under the
       demo latency SLO; mid-phase one worker is SIGKILLed.  The pool
       restarts it, the router rebalances its clients over the PR 1
       failover path — the gate is ZERO client errors (sheds allowed:
       rebalanced/shed traffic is the designed degradation) with the
       admitted-latency objective held.
    2. **autoscale-up leg**: offered load steps past the autoscaler's
       sustained admitted-rate watermark; after the hold, the fleet
       must provably spawn (serving count reaches N+1).
    3. **idle leg**: load stops; the ``fleet_idle`` below-threshold
       signal holds and the fleet must provably drain one worker back
       (route-away -> SIGTERM drain -> reap, PR 7 semantics).

    Rate thresholds derive from a live capacity probe through the
    router, so the same soak is honest on any host speed."""
    import threading as _threading
    import time as _time

    import numpy as np

    from nnstreamer_tpu.fleet import (Autoscaler, AutoscalerConfig,
                                      FleetLoop, TensorQueryRouter,
                                      WorkerPool,
                                      default_autoscaler_signals,
                                      launch_spawn_fn)
    from nnstreamer_tpu.obs.federation import (CollectorServer,
                                               MetricsCollector)
    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.obs.timeseries import (RingSampler,
                                               TimeSeriesRing)
    from nnstreamer_tpu.slo import (Evaluator, LoadGenerator,
                                    SLOMonitor, load_spec)

    os.makedirs(args.out, exist_ok=True)
    n = max(3, int(args.fleet_workers))
    duration = max(40.0, args.duration)
    phase_a = max(24.0, 0.5 * duration)
    phase_b = max(16.0, 0.3 * duration)
    clients = args.clients or 32
    payload = np.arange(4, dtype=np.float32)

    collector = MetricsCollector()
    collector_server = CollectorServer(collector, port=0)
    router = TensorQueryRouter(port=0, replicas=2, timeout=5.0,
                               collector=collector)
    pool = WorkerPool(
        launch_spawn_fn(FLEET_WORKER_TEMPLATE,
                        collector_port=collector_server.port,
                        push_interval_s=0.5,
                        drain_grace_s=args.fleet_drain_grace,
                        soak_s=duration + 600.0,
                        log_dir=os.path.join(args.out, "workers")),
        min_workers=n, max_workers=n + 1, collector=collector,
        restart_backoff_s=0.5, stale_kill_s=10.0,
        drain_grace_s=args.fleet_drain_grace,
        on_up=lambda w: router.add_worker(w.host, w.port),
        on_draining=lambda w: router.mark_draining(w.key),
        on_down=lambda w: router.remove_worker(w.key))

    ring = sampler = loop = None
    kill_info = {}
    try:
        pool.start()
        loop = FleetLoop([pool.tick], interval_s=0.5).start()
        deadline = _time.monotonic() + 180.0
        while pool.serving_count() < n and _time.monotonic() < deadline:
            _time.sleep(0.5)
        if pool.serving_count() < n:
            print(json.dumps({
                "metric": "soak_fleet", "verdict": "INFRA_DEAD",
                "pass": False, "status": "infra_dead",
                "vs_baseline": None,
                "reason": f"only {pool.serving_count()}/{n} workers "
                          "came up (see workers/*.log)"}), flush=True)
            return 2
        if not wait_query_ready("127.0.0.1", router.port, payload,
                                timeout_s=30.0):
            print(json.dumps({
                "metric": "soak_fleet", "verdict": "INFRA_DEAD",
                "pass": False, "status": "infra_dead",
                "vs_baseline": None,
                "reason": "router endpoint never served a round "
                          "trip"}), flush=True)
            return 2

        # honest thresholds on any host: probe the ROUTED capacity,
        # size phase A at ~30% of it (comfortably under the SLO), the
        # spawn watermark in the gap, and phase B past the watermark
        # but still under ~2/3 of capacity (the autoscale leg must
        # prove scaling on sustained RATE, not queueing collapse)
        measure_capacity("127.0.0.1", router.port, seconds=2.0,
                         payload=payload)                   # warm-up
        capacity = measure_capacity("127.0.0.1", router.port,
                                    seconds=3.0, payload=payload)
        rate_a = min(150.0, 0.30 * capacity)
        up_rps = 1.5 * rate_a
        rate_b = 2.2 * rate_a
        asc_cfg = AutoscalerConfig(
            rate_high_rps=up_rps, rate_low_rps=1.0,
            hold_s=4.0, idle_hold_s=6.0,
            spawn_cooldown_s=15.0, drain_cooldown_s=10.0,
            post_spawn_guard_s=10.0)
        ring = TimeSeriesRing(collector, interval_s=0.5,
                              retention_s=duration + 120.0,
                              registry=REGISTRY)
        from nnstreamer_tpu.query.server import DEFAULT_QUEUE_DEPTH

        signals = default_autoscaler_signals(
            ring, asc_cfg, queue_depth=DEFAULT_QUEUE_DEPTH)
        autoscaler = Autoscaler(pool, signals["up"], signals["down"],
                                cfg=asc_cfg).attach(ring)
        sampler = RingSampler(ring).start()
        loop.fns.append(autoscaler.tick)

        # -- phase 1: kill leg under the latency SLO ----------------------
        spec = load_spec(args.slo, duration_s=phase_a)
        evaluator = Evaluator(spec)
        monitor = SLOMonitor(evaluator)
        gen_a = LoadGenerator(
            "127.0.0.1", router.port, clients=clients,
            rate_hz=rate_a / clients, duration_s=phase_a,
            schedule=args.schedule, seed=args.seed,
            timeout=max(args.timeout, 3.0), payload=payload)

        def _kill_one():
            # SIGKILL (not the graceful SIGTERM): this leg proves the
            # CRASH path — no drain, no shed hints, just a dead socket
            # the failover legs must rotate through
            rows = [w for w in router.workers() if w["routed"]]
            key = (rows or router.workers())[0]["worker"]
            with pool._lock:
                victim = next((w for w in pool._workers.values()
                               if w.key == key), None)
            if victim is None:
                return
            kill_info.update({"worker": victim.key,
                              "wid": victim.wid,
                              "routed_at_kill": next(
                                  (r["routed"] for r in rows
                                   if r["worker"] == key), 0),
                              "at_s": round(_time.monotonic() - t0, 1)})
            victim.proc.kill()

        t0 = _time.monotonic()
        killer = _threading.Timer(0.4 * phase_a, _kill_one)
        killer.daemon = True
        killer.start()
        monitor.start()
        try:
            summary_a = gen_a.run()
        finally:
            killer.cancel()
            monitor.stop(final_tick=True)
        verdict_a = evaluator.verdict()
        # pool recovery: the respawned worker must be serving again
        deadline = _time.monotonic() + 60.0
        while pool.serving_count() < n and _time.monotonic() < deadline:
            _time.sleep(0.5)
        recovered = pool.serving_count() >= n

        # -- phase 2: sustained load -> spawn -----------------------------
        gen_b = LoadGenerator(
            "127.0.0.1", router.port, clients=clients,
            rate_hz=rate_b / clients, duration_s=phase_b,
            schedule=args.schedule, seed=args.seed + 1,
            timeout=max(args.timeout, 3.0), payload=payload)
        summary_b = gen_b.run()
        deadline = _time.monotonic() + 30.0
        while pool.serving_count() < n + 1 \
                and _time.monotonic() < deadline:
            _time.sleep(0.5)
        scaled_up = (autoscaler.spawns >= 1
                     and pool.serving_count() >= n + 1)

        # -- phase 3: idle -> drain ---------------------------------------
        deadline = _time.monotonic() + max(
            40.0, asc_cfg.idle_hold_s + asc_cfg.post_spawn_guard_s
            + 20.0)
        while (autoscaler.drains < 1
               or pool.serving_count() > n) \
                and _time.monotonic() < deadline:
            _time.sleep(0.5)
        scaled_down = (autoscaler.drains >= 1
                       and pool.serving_count() <= n)

        if sampler is not None:
            sampler.stop(final_capture=True)
            sampler = None
        checks = {
            "three_plus_workers": n >= 3,
            "zero_client_errors": summary_a["errors"] == 0
            and summary_b["errors"] == 0,
            "latency_slo_held": bool(verdict_a["pass"]),
            "worker_killed_mid_run": bool(kill_info),
            "pool_recovered": recovered,
            "spawn_on_sustained_load": scaled_up,
            "drain_on_idle": scaled_down,
        }
        verdict = {
            "metric": "soak_fleet", "status": "live",
            "pass": all(checks.values()),
            "verdict": "PASS" if all(checks.values()) else "FAIL",
            "checks": checks,
            "fleet": {
                "workers": n, "clients": clients,
                "capacity_routed_rps": round(capacity, 1),
                "rate_kill_leg_rps": round(rate_a, 1),
                "rate_autoscale_leg_rps": round(rate_b, 1),
                "spawn_watermark_rps": round(up_rps, 1),
                "drain_grace_s": args.fleet_drain_grace,
                "replicas": router.replicas,
            },
            "kill": kill_info,
            "kill_leg": {"loadgen": summary_a, "slo": verdict_a},
            "autoscale_leg": {"loadgen": summary_b},
            "router_workers": router.workers(),
            "pool_events": list(pool.events),
            "autoscaler": autoscaler.report(),
            "signals": ring.signal_report(),
            "federation_origins": collector.origins(),
        }
        with open(os.path.join(args.out, "verdict.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(verdict, fh, indent=2)
        line = {"metric": "soak_fleet", "verdict": verdict["verdict"],
                "pass": verdict["pass"], "status": "live",
                "workers": n,
                "kill": kill_info,
                "errors": summary_a["errors"] + summary_b["errors"],
                "sheds": summary_a.get("shed", 0)
                + summary_b.get("shed", 0),
                "kill_leg_latency_us": summary_a["latency_us"],
                "spawns": autoscaler.spawns,
                "drains": autoscaler.drains,
                "checks": checks,
                "artifact": os.path.join(args.out, "verdict.json")}
        print(json.dumps(line), flush=True)
        return 0 if verdict["pass"] else 1
    finally:
        if sampler is not None:
            sampler.stop(final_capture=False)
        if ring is not None:
            ring.close()
        if loop is not None:
            loop.stop()
        pool.stop(drain=False)
        router.close()
        collector_server.close()


def spawn_federated_worker(out_dir: str, data_port: int,
                           collector_port: int, soak_s: float,
                           push_interval_s: float = 0.5):
    """One out-of-process worker for the federated soak: the same demo
    serving pipeline, launched via ``launch.py --push-metrics`` so its
    registry streams into THIS process's collector.  Returns a Popen
    (SIGTERM drains it — launch.py installs the drain handler)."""
    import subprocess

    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    line = (f"tensor_query_serversrc name=qsrc id={FEDERATE_SERVER_ID} "
            f"port={data_port} caps={DEMO_CAPS} ! "
            "tensor_transform mode=arithmetic option=mul:2 ! "
            f"tensor_query_serversink id={FEDERATE_SERVER_ID}")
    log = open(os.path.join(out_dir, "worker.log"), "w",
               encoding="utf-8")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "nnstreamer_tpu.launch", line,
         "--soak", str(soak_s),
         "--push-metrics", f"127.0.0.1:{collector_port}",
         "--push-interval", str(push_interval_s), "--quiet"],
        stdout=log, stderr=log, env=env, cwd=root)
    proc._soak_log = log    # closed by stop_worker
    return proc


def stop_worker(proc, grace_s: float = 15.0) -> None:
    import signal

    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=grace_s)
    except Exception:   # noqa: BLE001 — hard stop after the grace
        proc.kill()
        proc.wait(timeout=10)
    proc._soak_log.close()


def wait_query_ready(host: str, port: int, payload,
                     timeout_s: float = 60.0, proc=None) -> bool:
    """Block until a query round trip succeeds against host:port.
    ``proc`` (the serving Popen) fails fast when the process died at
    startup instead of spinning out the whole timeout."""
    import time as _time

    import numpy as np

    from nnstreamer_tpu.query.client import QueryConnection
    from nnstreamer_tpu.tensor.buffer import TensorBuffer

    deadline = _time.monotonic() + timeout_s
    while _time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            return False
        try:
            conn = QueryConnection(host, port, timeout=10.0,
                                   max_retries=1)
            conn.connect()
            try:
                if conn.query(TensorBuffer(
                        tensors=[np.asarray(payload)])) is not None:
                    return True
            finally:
                conn.close()
        except (ConnectionError, TimeoutError, OSError):
            _time.sleep(0.25)
    return False


def default_signals(ring, queue_depth: int):
    """The standard sustained signals every soak watches — the same
    bus the fleet autoscaler will subscribe to (ROADMAP item 3):

    - ``sustained_shed``: shed fraction >= 0.2 held 5 s (disarm below
      0.1) — the server has been genuinely refusing work, not one hot
      scrape;
    - ``sustained_queue``: worst queue depth >= 75 % of the bound held
      5 s — backlog is structural, not a burst;
    - ``shed_burst``: windowed shed rate >= 5/s held 5 s — volume
      evidence next to the fraction.

    The clean ``--demo`` soak must record ZERO firings on all three
    (the false-positive gate); the ``--overload`` soak must fire
    ``sustained_shed`` (57 % bronze shed is the designed steady state).
    """
    from nnstreamer_tpu.obs.timeseries import SustainedSignal

    return [
        ring.add_signal(SustainedSignal(
            "sustained_shed", "nns_query_server_shed_rate",
            threshold=0.2, disarm_below=0.1, min_hold_s=5.0,
            kind="gauge", window_s=10.0)),
        ring.add_signal(SustainedSignal(
            "sustained_queue", "nns_query_server_queue_depth",
            threshold=max(1.0, 0.75 * queue_depth), min_hold_s=5.0,
            kind="gauge", window_s=10.0)),
        ring.add_signal(SustainedSignal(
            "shed_burst", "nns_query_server_shed_total",
            threshold=5.0, min_hold_s=5.0, kind="rate",
            window_s=10.0)),
    ]


def default_chaos(duration_s: float) -> str:
    """Demo chaos: a full connection kill at 35 % and a one-shot
    mid-stream disconnect at 60 % of the soak — both recoverable, so a
    healthy harness PASSES through them (the false-positive gate)."""
    return (f"{duration_s * 0.35:.1f}:kill;"
            f"{duration_s * 0.60:.1f}:disconnect_once")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="soak", description="open-loop SLO soak harness")
    ap.add_argument("--demo", action="store_true",
                    help="run against an in-process loopback serving "
                         "pipeline (default when --port is not given)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="existing QueryServer data port (0 = demo)")
    ap.add_argument("--clients", type=int, default=0,
                    help="concurrent query connections (default 64; "
                         "the --overload demo defaults to 32 — enough "
                         "concurrency to cross the shed watermarks, "
                         "few enough that the in-process harness's own "
                         "thread contention does not dominate the "
                         "measurement)")
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--rate", type=float, default=None,
                    help="arrivals/s PER CLIENT (offered load = "
                         "clients * rate).  Default: the demo measures "
                         "its target's CONCURRENT capacity live (the "
                         "--overload 8-conn closed-loop probe) and "
                         "self-sizes at ~50%% of it — so per-frame and "
                         "batching servers both soak at half of what "
                         "they really sustain; non-demo targets "
                         "default to 1.0.  Raising it past saturation "
                         "is itself a useful experiment — the "
                         "open-loop harness will show the queueing "
                         "collapse a closed-loop one hides")
    ap.add_argument("--schedule", choices=("poisson", "constant"),
                    default="poisson")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--timeout", type=float, default=2.0,
                    help="per-request reply budget (seconds)")
    ap.add_argument("--slo", default=None, metavar="FILE",
                    help="SLO spec JSON (default: demo spec scaled to "
                         "--duration)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="staged chaos 'at_s:fault[:duration[:value]];"
                         "...' (default: kill@35%% + disconnect@60%%; "
                         "'' disables)")
    ap.add_argument("--out", default="soak_out", metavar="DIR",
                    help="artifact dir (verdict.json + flight-recorder "
                         "bundles)")
    ap.add_argument("--force-breach", action="store_true",
                    help="add an impossible latency objective so the "
                         "breach/flight-recorder path fires")
    ap.add_argument("--overload", type=float, default=None,
                    metavar="FACTOR",
                    help="overload acceptance mode: measure capacity "
                         "closed-loop, offer FACTOR x capacity with "
                         "QoS classes gold:silver:bronze 1:2:5 "
                         "(per-client), and gate on the admission "
                         "invariants (bounded queue, explicit sheds, "
                         "closed breakers, admitted p99 within SLO); "
                         "chaos defaults OFF here so the shed "
                         "bookkeeping is exact")
    ap.add_argument("--xbatch", type=int, default=None, metavar="BUCKET",
                    help="cross-stream batching acceptance mode "
                         "(query/server.py batch=): measure a "
                         "per-frame MLP serving pipeline's concurrent "
                         "capacity, rebuild it with batch=BUCKET, soak "
                         "the batching server at >=4x the per-frame "
                         "capacity under the same SLO spec, and gate "
                         "on rps and admission-wait vs the PR 8 "
                         "per-frame streaming baseline")
    ap.add_argument("--federate", action="store_true",
                    help="telemetry-federation acceptance mode (demo "
                         "only): spawn a SECOND serving process "
                         "(launch.py --push-metrics) next to the "
                         "in-process demo server, drive load at both, "
                         "serve ONE federated /metrics endpoint "
                         "(obs/federation.py collector) whose scrape "
                         "shows both origins, and record the federated "
                         "per-origin timeline in the flight recorder "
                         "so a breach bundle shows both sides")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet acceptance mode (fleet/): spawn a "
                         "router + >=3 out-of-process launch.py "
                         "workers federating into this process's "
                         "collector, soak through a mid-run worker "
                         "SIGKILL (gate: zero client errors, latency "
                         "SLO held), then prove the autoscaler spawns "
                         "on sustained load and drains on idle")
    ap.add_argument("--fleet-workers", type=int, default=3,
                    help="initial fleet size for --fleet (min 3; the "
                         "autoscale leg scales to N+1 and back)")
    ap.add_argument("--fleet-drain-grace", type=float, default=5.0,
                    help="worker SIGTERM drain budget for --fleet "
                         "scale-downs (seconds)")
    ap.add_argument("--llm", action="store_true",
                    help="token-streaming LLM serving acceptance soak "
                         "(ISSUE 15): multi-client continuous-batching "
                         "token streams with heterogeneous prompt/"
                         "output lengths through tensor_llm — gates "
                         "zero errors, exact per-client order, bounded "
                         "cache memory, explicit sheds, >=2x the solo "
                         "baseline, conserved prefill/decode "
                         "attribution, plus the token_latency block "
                         "(ISSUE 20): per-class TTFT/ITL with ttft/"
                         "itl SLO objectives gating the verdict and "
                         "per-session blame conservation")
    ap.add_argument("--llm-slots", type=int, default=12,
                    help="--llm: KV-cache slots (sessions resident)")
    ap.add_argument("--llm-batch", type=int, default=8,
                    help="--llm: decode bucket capacity")
    ap.add_argument("--llm-paged", action="store_true",
                    help="paged-KV serving acceptance soak (ISSUE 17): "
                         "short-chat mix against the block-paged arena "
                         "at dense arena bytes — gates >=2x resident "
                         "sessions vs dense, probe byte-identity to "
                         "the dense server, warm-phase prefix-cache "
                         "hits with prefill share below the cold "
                         "phase, chunked-prefill interleave, zero "
                         "steady-state compiles, zero page leaks, "
                         "and (ISSUE 20) ttft/itl SLO objectives "
                         "with warm-prefix TTFT measured below cold "
                         "inside the same run")
    ap.add_argument("--xbatch-timeout-ms", type=float, default=30.0,
                    help="batch-timeout-ms for the --xbatch server.  "
                         "Default 30 (deadline mode): the soak's "
                         "clients are SYNCHRONOUS — one outstanding "
                         "frame each — so greedy collect (0) races "
                         "their next sends right after the reply "
                         "split and degenerates into tiny convoy-"
                         "fragment buckets (see PERFORMANCE.md); a "
                         "small fill window lets the convoy re-arrive")
    args = ap.parse_args(argv)

    from nnstreamer_tpu.slo import (Evaluator, FlightRecorder,
                                    LoadGenerator, SLOMonitor, load_spec)
    from nnstreamer_tpu.slo.spec import Objective, SLOSpec
    from nnstreamer_tpu.testing.faults import ChaosProxy, ChaosSchedule

    if args.xbatch is not None:
        return run_xbatch(args, ap)
    if args.fleet:
        return run_fleet(args, ap)
    if args.llm_paged:
        return run_llm_paged(args, ap)
    if args.llm:
        return run_llm(args, ap)

    os.makedirs(args.out, exist_ok=True)
    demo = args.demo or not args.port
    if args.federate and not demo:
        ap.error("--federate requires the --demo target (the collector "
                 "and its federated endpoint live in the soak process)")
    server = tracer = None
    collector = collector_server = worker = None
    fed_endpoint = None
    sampler = ring = None
    try:
        if demo:
            # overload mode bounds the demo queue to the latency
            # budget (12 frames * 10 ms service = 120 ms of nominal
            # backlog, under the demo SLO's 250 ms p99 even when
            # contention stretches the real service time — beyond the
            # bound, shedding, not queueing, absorbs excess) over a
            # 10 ms service time whose 2x overload the in-process
            # harness can honestly offer (see _register_delay_element)
            overload_demo = args.overload is not None
            server, port, tracer = build_demo_server(
                queue_depth=12 if overload_demo else 0,
                service_ms=10.0 if overload_demo else 0.0)
            host = "127.0.0.1"
        else:
            host, port = args.host, args.port

        # a dead target is status infra_dead, exit 2, and must never
        # masquerade as an SLO FAIL
        diagnosis = diagnose_endpoint(host, port,
                                      timeout=min(5.0, args.timeout * 2))
        if not diagnosis["ok"]:
            row = {"metric": "soak_verdict", "verdict": "INFRA_DEAD",
                   "pass": False, "status": "infra_dead",
                   "vs_baseline": None, "diagnosis": diagnosis}
            print(json.dumps(row), flush=True)
            return 2

        worker_port = None
        if args.federate:
            # the soak process IS the collector: local registry (the
            # demo server's gauges) merges as its own origin next to
            # the pushed worker origins, and ONE endpoint serves the
            # merged view (obs/federation.py)
            from nnstreamer_tpu.obs.federation import (CollectorServer,
                                                       MetricsCollector)
            from nnstreamer_tpu.obs.httpd import start_metrics_server

            from nnstreamer_tpu.obs.httpd import stop_metrics_server

            collector = MetricsCollector()
            collector.register_health()
            collector_server = CollectorServer(collector, port=0)
            # the process singleton may already be claimed (a set
            # NNS_METRICS_PORT armed it at the demo pipeline's play(),
            # bound to the PLAIN registry) — and start_metrics_server
            # is idempotent, so without this the "federated" endpoint
            # would silently serve origin-less metrics and fail the
            # scrape check on a perfectly healthy run
            stop_metrics_server()
            fed_endpoint = start_metrics_server(0, registry=collector)
            worker_port = _free_port()
            worker = spawn_federated_worker(
                os.path.join(args.out, "worker"), worker_port,
                collector_server.port, soak_s=args.duration + 60.0)
            import numpy as np

            if not wait_query_ready("127.0.0.1", worker_port,
                                    np.arange(4, dtype=np.float32),
                                    proc=worker):
                print(json.dumps({
                    "metric": "soak_verdict", "verdict": "INFRA_DEAD",
                    "pass": False, "status": "infra_dead",
                    "vs_baseline": None,
                    "reason": "federated worker never came up "
                              "(see worker/worker.log)"}), flush=True)
                return 2

        spec = load_spec(args.slo, duration_s=args.duration)
        if args.force_breach:
            spec = SLOSpec(
                name=spec.name + "+forced-breach",
                objectives=spec.objectives + (Objective(
                    "forced_p99", "latency", target=0.9,
                    threshold_us=1.0),),
                window_fast_s=spec.window_fast_s,
                window_slow_s=spec.window_slow_s,
                burn_threshold=spec.burn_threshold,
                tick_s=spec.tick_s)

        overload = args.overload is not None
        clients = args.clients or (32 if overload else 64)
        timeout = args.timeout
        rate = args.rate
        if rate is None and not overload:
            if demo:
                # satellite: self-size at ~50% of the MEASURED
                # concurrent capacity (8-conn probe) — works unchanged
                # whether the target is a per-frame or a batching
                # server, where any hard-coded per-query constant would
                # be wrong by the bucket fill factor
                cap_probe = measure_capacity(host, port, seconds=2.0)
                rate = demo_rate_from_capacity(cap_probe, clients)
            else:
                rate = 1.0
        classes = (("interactive", 0.75), ("batch", 0.25))
        capacity = None
        if overload:
            if args.overload <= 0:
                ap.error("--overload FACTOR must be > 0")
            if not demo:
                # the overload invariants (queue bound, shed counter
                # match, slab pool) need in-process server
                # introspection — an external target would silently
                # skip EVERY check and print an unearned PASS
                ap.error("--overload requires the in-process --demo "
                         "target (its checks introspect the demo "
                         "QueryServer); drive external servers with "
                         "the plain loadgen + --slo instead")
            capacity = measure_capacity(host, port)
            rate = args.overload * capacity / clients
            # the acceptance mix: gold:silver:bronze 1:2:5 per CLIENT;
            # a generous per-request budget so queued-but-admitted
            # requests never time out (a timeout would orphan its
            # T_SHED/REPLY and break the exact shed bookkeeping)
            classes = (("gold", 1.0), ("silver", 2.0), ("bronze", 5.0))
            timeout = max(timeout, 5.0)

        proxy = ChaosProxy((host, port))
        # overload mode defaults chaos OFF: a mid-soak kill drops
        # in-flight T_SHEDs and would break the exact client==server
        # shed bookkeeping the acceptance check asserts
        chaos_spec = (("" if overload else default_chaos(args.duration))
                      if args.chaos is None else args.chaos)
        schedule = ChaosSchedule.parse(proxy, chaos_spec)

        recorder = FlightRecorder(args.out, tracer=tracer,
                                  collector=collector)
        evaluator = Evaluator(spec, on_breach=recorder.on_breach)
        evaluator.on_tick = recorder.record
        monitor = SLOMonitor(evaluator)

        # sustained-signal watch (obs/timeseries.py): the ring runs
        # over the FEDERATED view when one exists — fleet-wide shed /
        # queue evidence — else the local registry.  The clean demo
        # must end with zero firings; the overload run must fire
        # sustained_shed (its designed steady state IS sustained shed).
        from nnstreamer_tpu.obs.metrics import REGISTRY
        from nnstreamer_tpu.obs.timeseries import (RingSampler,
                                                   TimeSeriesRing)

        ring = TimeSeriesRing(
            collector if collector is not None else REGISTRY,
            interval_s=1.0,
            retention_s=max(60.0, args.duration + 10.0),
            registry=REGISTRY)
        from nnstreamer_tpu.query.server import DEFAULT_QUEUE_DEPTH

        demo_depth = 12 if overload else DEFAULT_QUEUE_DEPTH
        default_signals(ring, demo_depth)
        sampler = RingSampler(ring).start()

        gen = LoadGenerator(
            proxy.host, proxy.port, clients=clients,
            rate_hz=rate, duration_s=args.duration,
            schedule=args.schedule, seed=args.seed,
            timeout=timeout,
            classes=classes, qos=overload)
        worker_gen = None
        if args.federate:
            # the worker origin must show LIVE traffic on the federated
            # endpoint, not just registered gauges: a quarter of the
            # client population drives it directly (chaos stays on the
            # primary so its bookkeeping is undisturbed)
            worker_gen = LoadGenerator(
                "127.0.0.1", worker_port,
                clients=max(4, clients // 4), rate_hz=rate,
                duration_s=args.duration, schedule=args.schedule,
                seed=args.seed + 1, timeout=timeout, classes=classes)

        probe = None
        if overload:
            import resource

            from nnstreamer_tpu.query.resilience import STATS
            rss_before_kb = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss
            stats_before = STATS.snapshot()
            probe = BreakerProbe(proxy.host, proxy.port).start()

        schedule.start()
        monitor.start()
        wthread = wsummary = None
        if worker_gen is not None:
            import threading as _threading

            wresult = {}

            def _drive_worker():
                wresult["summary"] = worker_gen.run()

            wthread = _threading.Thread(target=_drive_worker,
                                        daemon=True,
                                        name="federated-loadgen")
            wthread.start()
        try:
            summary = gen.run()
        finally:
            if wthread is not None:
                wthread.join(timeout=args.duration + 60.0)
                wsummary = wresult.get("summary")
            monitor.stop(final_tick=True)
            probe_stats = probe.stop() if probe is not None else None
            schedule.stop()
            proxy.close()

        federation = None
        if args.federate:
            # scrape the ONE federated endpoint while BOTH origins are
            # still live: the acceptance is that a single GET shows
            # both processes' gauges under correct origin labels
            import urllib.request

            from nnstreamer_tpu.obs.dashboard import (key_labels,
                                                      parse_prometheus)

            fed_port = fed_endpoint.server_address[1]
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{fed_port}/metrics",
                        timeout=5) as resp:
                    scraped = parse_prometheus(
                        resp.read().decode("utf-8", "replace"))
            except OSError:
                scraped = {}
            per_origin = {}
            for key in scraped:
                o = key_labels(key).get("origin")
                if o:
                    per_origin[o] = per_origin.get(o, 0) + 1
            origins = collector.origins()
            federation = {
                "endpoint_port": fed_port,
                "collector_port": collector_server.port,
                "origins": origins,
                "scraped_series_by_origin": per_origin,
                "worker_loadgen": wsummary,
                "checks": {
                    "two_origins_live": len(origins) >= 2,
                    "scrape_shows_all_origins":
                        len(per_origin) >= 2 and
                        all(n > 0 for n in per_origin.values()),
                    "worker_traffic_ok": bool(
                        wsummary and wsummary.get("ok", 0) > 0
                        and not wsummary.get("errors", 1)),
                },
            }
            federation["pass"] = all(federation["checks"].values())

        if sampler is not None:
            sampler.stop(final_capture=True)

        verdict = evaluator.verdict()
        verdict["status"] = "live"
        verdict["loadgen"] = summary
        if ring is not None:
            verdict["signals"] = ring.signal_report()
        if federation is not None:
            verdict["federation"] = federation
            verdict["pass"] = verdict["pass"] and federation["pass"]
            verdict["verdict"] = "PASS" if verdict["pass"] else "FAIL"
        from nnstreamer_tpu.obs.profile import attribution_block

        attribution = attribution_block(tracer)
        if attribution:
            # where the serving pipeline's frame time went during the
            # soak (wait-state blame, obs/attrib.py): the queueing
            # states here should explain any slo-vs-service latency
            # divergence the objectives saw
            verdict["attribution"] = attribution
        verdict["chaos"] = schedule.log
        verdict["flight_recorder"] = {"bundles": recorder.dumps}
        if overload:
            from nnstreamer_tpu.query.resilience import STATS
            from nnstreamer_tpu.query.server import get_server

            opens = STATS.delta(stats_before).get("breaker.open", 0)
            srv = get_server(DEMO_SERVER_ID) if demo else None
            if srv is not None:
                verdict["overload"] = overload_checks(
                    srv, summary, opens, rss_before_kb,
                    verdict["pass"], probe_stats)
                verdict["overload"]["capacity_rps"] = round(capacity, 1)
                verdict["overload"]["factor"] = args.overload
                verdict["overload"]["offered_rps"] = round(
                    rate * clients, 1)
                verdict["pass"] = verdict["overload"]["pass"]
                verdict["verdict"] = ("PASS" if verdict["pass"]
                                      else "FAIL")
        with open(os.path.join(args.out, "verdict.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(verdict, fh, indent=2)
        line = {
            "metric": "soak_verdict", "verdict": verdict["verdict"],
            "pass": verdict["pass"], "status": "live",
            "clients": summary["clients"],
            "peak_live_clients": summary["peak_live_clients"],
            "duration_s": summary["duration_s"],
            "sent": summary["sent"], "errors": summary["errors"],
            "error_fraction": summary["error_fraction"],
            "latency_us": summary["latency_us"],
            "breaches": len(verdict["breaches"]),
            "chaos_events": len(schedule.log),
            "bundles": recorder.dumps,
            "artifact": os.path.join(args.out, "verdict.json"),
        }
        if ring is not None:
            line["signals"] = {
                "firings": verdict["signals"]["firings"],
                "fired": verdict["signals"]["fired"]}
        if federation is not None:
            line["federation"] = {
                "pass": federation["pass"],
                "origins": [o["origin"] for o in federation["origins"]],
                "scraped_series_by_origin":
                    federation["scraped_series_by_origin"],
                "checks": federation["checks"]}
        if attribution:
            line["attribution"] = {
                "top": attribution["top"],
                "attributed_pct": attribution["attributed_pct"]}
        if "overload" in verdict:
            ov = verdict["overload"]
            line["overload"] = {
                "capacity_rps": ov["capacity_rps"],
                "factor": ov["factor"],
                "offered_rps": ov["offered_rps"],
                "shed_fraction": ov["shed_fraction"],
                "shed_by_class": ov["shed_by_class"],
                "peak_incoming_depth": ov["peak_incoming_depth"],
                "checks": ov["checks"],
            }
        print(json.dumps(line), flush=True)
        return 0 if verdict["pass"] else 1
    finally:
        if sampler is not None:
            sampler.stop(final_capture=False)
        if ring is not None:
            ring.close()
        if worker is not None:
            stop_worker(worker)
        if fed_endpoint is not None:
            from nnstreamer_tpu.obs.httpd import stop_metrics_server

            stop_metrics_server()
        if collector_server is not None:
            collector_server.close()
        if server is not None:
            server.stop()
            from nnstreamer_tpu.query.server import shutdown_server

            shutdown_server(DEMO_SERVER_ID)


if __name__ == "__main__":
    raise SystemExit(main())
