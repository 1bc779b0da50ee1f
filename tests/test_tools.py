"""Dev-tool tests: custom-filter codegen + pbtxt pipeline converter.

Role parity with the reference's tools/development
(nnstreamerCodeGenCustomFilter.py, gstPrototxt.py + parser/)."""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import gen_custom_filter  # noqa: E402
import pbtxt_pipeline  # noqa: E402


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCodegen:
    def test_easy_skeleton_runs_in_pipeline(self, tmp_path):
        path = tmp_path / "myfilt.py"
        code = gen_custom_filter.generate(
            "gen-easy-test", ["4:4,float32"], ["4:4,float32"],
            mode="easy", modname="myfilt")
        path.write_text(code)
        mod = _load_module(path, "myfilt")
        mod.register()
        try:
            from nnstreamer_tpu import parse_launch
            from nnstreamer_tpu.tensor.buffer import TensorBuffer

            p = parse_launch(
                "appsrc caps=other/tensors,format=static,num_tensors=1,"
                "dimensions=4:4,types=float32,framerate=0/1 name=in ! "
                "tensor_filter framework=custom-easy model=gen-easy-test ! "
                "tensor_sink name=out")
            got = []
            p.get("out").connect("new-data", lambda b: got.append(b.np(0)))
            p.play()
            p.get("in").push_buffer(TensorBuffer(
                tensors=[np.ones((4, 4), np.float32)]))
            p.get("in").end_of_stream()
            p.wait(timeout=60)
            p.stop()
            assert len(got) == 1 and got[0].shape == (4, 4)
        finally:
            from nnstreamer_tpu.filter.backends.custom import \
                unregister_custom_easy

            unregister_custom_easy("gen-easy-test")

    def test_framework_skeleton_registers(self, tmp_path):
        path = tmp_path / "fwfilt.py"
        code = gen_custom_filter.generate(
            "gen-fw-test", ["2:3,uint8"], ["5,float32"], mode="framework")
        path.write_text(code)
        _load_module(path, "fwfilt")
        from nnstreamer_tpu.filter.framework import (FilterProperties,
                                                     open_backend)

        fw = open_backend(FilterProperties(framework="gen-fw-test",
                                           model="demo"))
        try:
            ii, oi = fw.get_model_info()
            assert ii[0].np_shape == (3, 2) and oi[0].np_shape == (5,)
            outs = fw.invoke([np.zeros((3, 2), np.uint8)])
            assert outs[0].shape == (5,)
        finally:
            fw.close()

    def test_cli_writes_file(self, tmp_path):
        out = tmp_path / "cli.py"
        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..", "tools",
                          "gen_custom_filter.py"),
             "cli-test", "--in", "8,float32", "--out", "8,float32",
             "-o", str(out)], capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "register_custom_easy" in out.read_text()


class TestPbtxt:
    LAUNCH = ("videotestsrc num-buffers=3 ! "
              "video/x-raw,format=RGB,width=16,height=16,framerate=30/1 ! "
              "tensor_converter ! tensor_sink name=out")

    def test_roundtrip_runs(self):
        nodes = pbtxt_pipeline.parse_launch_text(self.LAUNCH)
        text = pbtxt_pipeline.to_pbtxt(nodes)
        assert 'element: "tensor_converter"' in text
        launch2 = pbtxt_pipeline.to_launch(pbtxt_pipeline.parse_pbtxt(text))
        from nnstreamer_tpu import parse_launch

        p = parse_launch(launch2)
        got = []
        p.get("out").connect("new-data", lambda b: got.append(1))
        p.run(timeout=60)
        assert len(got) == 3

    def test_fanout_tee_roundtrip(self):
        launch = ("videotestsrc num-buffers=2 name=s ! "
                  "video/x-raw,format=GRAY8,width=4,height=4,framerate=0/1 ! "
                  "tensor_converter ! tee name=t ! tensor_sink name=a  "
                  "t. ! tensor_sink name=b")
        nodes = pbtxt_pipeline.parse_launch_text(launch)
        text = pbtxt_pipeline.to_pbtxt(nodes)
        launch2 = pbtxt_pipeline.to_launch(pbtxt_pipeline.parse_pbtxt(text))
        from nnstreamer_tpu import parse_launch

        p = parse_launch(launch2)
        got = {"a": 0, "b": 0}
        p.get("a").connect("new-data",
                           lambda b: got.__setitem__("a", got["a"] + 1))
        p.get("b").connect("new-data",
                           lambda b: got.__setitem__("b", got["b"] + 1))
        p.run(timeout=60)
        assert got == {"a": 2, "b": 2}

    def test_mux_join_roundtrip_text(self):
        launch = ("appsrc name=s1 ! tensor_mux name=m ! tensor_sink  "
                  "appsrc name=s2 ! m.")
        nodes = pbtxt_pipeline.parse_launch_text(launch)
        # mux has two inputs
        mux = [n for n in nodes if n.element == "tensor_mux"][0]
        assert len(mux.inputs) == 2
        text = pbtxt_pipeline.to_pbtxt(nodes)
        nodes2 = pbtxt_pipeline.parse_pbtxt(text)
        mux2 = [n for n in nodes2 if n.element == "tensor_mux"][0]
        assert sorted(mux2.inputs) == sorted(mux.inputs)


def test_pbtxt_named_pads_order_fanin():
    """mux.sink_K refs slot fan-in inputs by index even when the launch
    string lists them out of order."""
    nodes = pbtxt_pipeline.parse_launch_text(
        "tensor_mux name=mux ! fakesink "
        "appsrc name=b ! mux.sink_1 "
        "appsrc name=a ! mux.sink_0")
    mux = next(n for n in nodes if n.name == "mux")
    assert mux.inputs == ["a", "b"]


def test_pbtxt_mixed_chain_and_pad_refs():
    """An in-chain link and an indexed ref mix correctly: sink_0 wins
    slot 0 even though the chain link was parsed first."""
    nodes = pbtxt_pipeline.parse_launch_text(
        "appsrc name=a ! tensor_mux name=mux ! fakesink "
        "appsrc name=b ! mux.sink_0")
    mux = next(n for n in nodes if n.name == "mux")
    assert mux.inputs == ["b", "a"]


def test_pbtxt_explicit_index_is_absolute_slot():
    """sink_1 with no sink_0 ref: the un-indexed chain link fills slot 0
    and the explicit ref lands at its ABSOLUTE position 1 (the round-3
    advisor case: it used to be treated as relative order → slot 0)."""
    nodes = pbtxt_pipeline.parse_launch_text(
        "appsrc name=a ! tensor_mux name=mux ! fakesink "
        "appsrc name=b ! mux.sink_1")
    mux = next(n for n in nodes if n.name == "mux")
    assert mux.inputs == ["a", "b"]


def test_pbtxt_unhonorable_explicit_index_errors():
    import pytest

    with pytest.raises(ValueError, match="cannot honor"):
        pbtxt_pipeline.parse_launch_text(
            "tensor_mux name=mux ! fakesink "
            "appsrc name=b ! mux.sink_2")


def test_pbtxt_duplicate_explicit_index_errors():
    import pytest

    with pytest.raises(ValueError, match="connected twice"):
        pbtxt_pipeline.parse_launch_text(
            "tensor_mux name=mux ! fakesink "
            "appsrc name=a ! mux.sink_0 appsrc name=b ! mux.sink_0")


TOOLS = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                     "tools"))


class TestPbtxtRoundTripCorpus:
    """Generative round-trip over the verbatim launch-line corpus this
    round's compat sweep established: launch → pbtxt → parse → launch →
    pbtxt must be a FIXED POINT (same graph: elements, props, links) —
    the property the reference's gstPrototxt converter pair guarantees."""

    CORPUS = [
        "videotestsrc num-buffers=3 pattern=13 ! "
        "video/x-raw,format=RGB,width=64,height=48,framerate=30/1 ! "
        "tensor_converter ! tensor_sink name=out",
        "appsrc name=s1 ! mux.sink_0  appsrc name=s2 ! mux.sink_1  "
        "tensor_mux name=mux sync-mode=slowest ! fakesink",
        "videotestsrc ! tee name=t ! tensor_converter ! fakesink  "
        "t. ! fakesink",
        "tensor_merge name=m mode=linear option=2 silent=true "
        "sync-mode=basepad sync-option=0:0.  appsrc name=a ! m.sink_0  "
        "appsrc name=b ! m.sink_1  m. ! fakesink",
        "videotestsrc num-buffers=1 ! "
        "video/x-raw,format=RGB,width=4,height=4,framerate=30/1 ! "
        "tensor_converter ! tensor_transform mode=arithmetic "
        "option=per-channel:true@0,add:255@0 ! fakesink",
        "multifilesrc location=x.%d start-index=0 stop-index=2 "
        "caps=application/octet-stream ! tensor_converter "
        "input-dim=3:4:4 input-type=uint8 ! tensor_sink name=o",
        "tensor_if name=tif compared-value=TENSOR_AVERAGE_VALUE "
        "compared-value-option=0 supplied-value=100 operator=LT "
        "then=PASSTHROUGH else=SKIP  appsrc name=s ! tif. "
        "tif. ! tensor_sink name=o",
    ]

    def test_fixed_point(self):
        import pbtxt_pipeline as pp

        for line in self.CORPUS:
            nodes1 = pp.parse_launch_text(line)
            text1 = pp.to_pbtxt(nodes1)
            nodes2 = pp.parse_pbtxt(text1)
            launch2 = pp.to_launch(nodes2)
            nodes3 = pp.parse_launch_text(launch2)
            text2 = pp.to_pbtxt(nodes3)
            # names may be generated, so compare name-independent
            # structure: element kinds, props, and input DEGREES
            g1 = [(n.element, tuple(sorted(n.props)), len(n.inputs))
                  for n in nodes1]
            g3 = [(n.element, tuple(sorted(n.props)), len(n.inputs))
                  for n in nodes3]
            assert sorted(g1) == sorted(g3), line
            assert text1.count("input:") == text2.count("input:"), line

    def test_unnamed_node_references_round_trip(self):
        """to_launch must emit name= for any node it references as
        'name.' — a generated __idN reference without the name would
        silently re-bind to whichever node regenerates that counter."""
        import pbtxt_pipeline as pp

        pbtxt = (
            'node { name: "x" element: "appsrc" }\n'
            'node { element: "appsrc" }\n'
            'node { name: "m" element: "tensor_mux" input: "__id1" '
            'input: "x" }\n'
            'node { element: "fakesink" input: "m" }\n')
        back = pp.parse_launch_text(pp.to_launch(pp.parse_pbtxt(pbtxt)))
        m = next(n for n in back if n.element == "tensor_mux")
        srcs = [next(n for n in back if n.name == i).element
                for i in m.inputs]
        assert srcs == ["appsrc", "appsrc"]
        fs = next(n for n in back if n.element == "fakesink")
        assert [next(n for n in back if n.name == i).element
                for i in fs.inputs] == ["tensor_mux"]

    def test_converter_parity_with_runtime_parser_errors(self):
        """Strings the RUNTIME parser rejects must not convert into a
        silently-wrong graph: src-pad branch refs (inexpressible in the
        positional model), dangling refs, and trailing '!' are named
        errors."""
        import pbtxt_pipeline as pp

        for bad, match in [
            ("tee name=t  t.src_1 ! mux.sink_0  tensor_mux name=mux ! "
             "fakesink", "src-pad"),
            ("a. fakesink", "never linked"),
            ("videotestsrc ! fakesink  t.", "never linked"),
            ("videotestsrc !", "ends with"),
        ]:
            with pytest.raises(ValueError, match=match):
                pp.parse_launch_text(bad)


class TestNnsTop:
    """obs/dashboard.py rendering + tools/nns_top.py CLI: the frame
    builder and renderer are pure functions of flat samples, so the
    tests pin them on synthetic histories; the CLI is driven --once
    against a real federated endpoint."""

    def _samples(self):
        """A 6-tick synthetic history: rising admitted counter, a shed
        burst, a queue filling, one element's occupancy, a fired
        signal, two origins."""
        base = {
            'nns_query_server_admitted_total{origin="a:1",qos="gold"}':
                0.0,
            'nns_query_server_shed_total{origin="a:1",qos="bronze"}':
                0.0,
            'nns_query_server_queue_depth{origin="a:1"}': 0.0,
            'nns_element_occupancy{element="f",origin="a:1"}': 0.82,
            'nns_element_proctime_us{element="f",quantile="0.99"}':
                1234.0,
            'nns_mfu{origin="a:1"}': 0.126,
            'nns_signal_state{signal="sustained_shed",origin="a:1"}':
                2.0,
            'nns_query_server_clients{origin="b:2"}': 8.0,
        }
        samples = []
        for t in range(6):
            flat = dict(base)
            flat['nns_query_server_admitted_total{origin="a:1",'
                 'qos="gold"}'] = 50.0 * t
            flat['nns_query_server_shed_total{origin="a:1",'
                 'qos="bronze"}'] = 5.0 * t
            flat['nns_query_server_queue_depth{origin="a:1"}'] = \
                float(t)
            samples.append((float(t), flat))
        return samples

    def test_build_view_rates_and_sections(self):
        from nnstreamer_tpu.obs.dashboard import build_view

        view = build_view(self._samples(), window_s=10.0)
        rates = {r["label"]: r for r in view["rates"]}
        assert rates["admitted"]["rate"] == pytest.approx(50.0)
        assert rates["shed"]["rate"] == pytest.approx(5.0)
        gauges = {g["label"]: g for g in view["gauges"]}
        assert gauges["queue depth"]["value"] == 5.0
        assert gauges["mfu"]["value"] == pytest.approx(0.126)
        assert gauges["clients"]["value"] == 8.0
        # origins derived from labels when no collector rows given
        assert [o["origin"] for o in view["origins"]] == ["a:1", "b:2"]
        [el] = view["elements"]
        assert el["element"] == "f"
        assert el["occupancy"] == pytest.approx(0.82)
        assert el["p99_us"] == 1234.0
        [sig] = view["signals"]
        assert sig["signal"] == "sustained_shed"
        assert sig["state"] == "FIRED"

    def test_render_frame_text(self):
        from nnstreamer_tpu.obs.dashboard import build_view, render_frame

        text = render_frame(build_view(self._samples(), window_s=10.0),
                            clock=0.0)
        assert "nns-top" in text
        assert "admitted" in text and "shed" in text
        assert "a:1" in text and "b:2" in text
        assert "sustained_shed=FIRED" in text
        assert "mfu" in text
        # counter restarts must never render negative rates
        from nnstreamer_tpu.obs.dashboard import _rate

        samples = [(0.0, {"nns_x_total": 100.0}),
                   (1.0, {"nns_x_total": 3.0})]
        assert _rate(samples, "nns_x_total", 10.0) == 0.0

    def test_sparkline_and_bar(self):
        from nnstreamer_tpu.obs.dashboard import bar, sparkline

        assert sparkline([]) == " " * 16
        s = sparkline([0, 1, 2, 3], width=4)
        assert len(s) == 4 and s[0] != s[-1]
        assert bar(0.5, width=10) == "[#####.....]"
        assert bar(2.0, width=4) == "[####]"      # clamped

    def test_ring_source_round_trip(self):
        """RingSource: a real TimeSeriesRing + signal report renders
        without a wire."""
        from nnstreamer_tpu.obs.dashboard import RingSource, TopLoop
        from nnstreamer_tpu.obs.metrics import MetricsRegistry
        from nnstreamer_tpu.obs.timeseries import (SustainedSignal,
                                                   TimeSeriesRing)

        r = MetricsRegistry()
        g = r.gauge("nns_query_server_shed_rate", fn=None)
        ring = TimeSeriesRing(r, registry=r)
        ring.add_signal(SustainedSignal(
            "shed", "nns_query_server_shed_rate", threshold=0.2,
            min_hold_s=0.0, kind="gauge"))
        g.set(0.9)
        for t in range(3):
            ring.capture(now=float(t))
        loop = TopLoop(RingSource(ring, label="test"), ansi=False)
        text = loop.render_once()
        assert "shed=fired(x1)" in text or "shed=fired" in text
        assert "test" in text

    def test_cli_once_against_federated_endpoint(self):
        """tools/nns_top.py --once scrapes a live federated endpoint
        and renders both origins."""
        import json as _json

        from nnstreamer_tpu.obs.federation import (CollectorServer,
                                                   MetricsCollector)
        from nnstreamer_tpu.obs.metrics import MetricsRegistry

        local = MetricsRegistry()
        local.gauge("nns_query_server_queue_depth", fn=None).set(3.0)
        col = MetricsCollector(registry=local, local_origin="loc:1")
        col.ingest({"origin": "rem:2", "seq": 1, "epoch": "e",
                    "full": True, "wall_us": 0, "offset_us": 0,
                    "health": "serving",
                    "state": {"nns_mfu": {"kind": "gauge",
                                          "value": 0.2}}})
        import http.server
        import threading

        # a private endpoint instance (the process singleton may be in
        # use by other tests): serve the collector's rendering directly
        class H(http.server.BaseHTTPRequestHandler):
            def do_GET(self):   # noqa: N802
                body = col.render_prometheus().encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        try:
            r = subprocess.run(
                [sys.executable, os.path.join(TOOLS, "nns_top.py"),
                 "--port", str(httpd.server_address[1]), "--once"],
                capture_output=True, text=True, timeout=60,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            assert r.returncode == 0, r.stdout + r.stderr
            assert "loc:1" in r.stdout and "rem:2" in r.stdout
            assert "queue depth" in r.stdout
            assert "mfu" in r.stdout
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_cli_once_dead_endpoint_exits_1(self):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "nns_top.py"),
             "--url", "127.0.0.1:1", "--once"],
            capture_output=True, text=True, timeout=60,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 1

    def test_parse_prometheus_timestamps_and_spacey_labels(self):
        from nnstreamer_tpu.obs.dashboard import parse_prometheus

        flat = parse_prometheus(
            'nns_a{l="x y"} 12 1718000000000\n'
            "nns_b 3.5\n"
            "# HELP nns_c nope\n"
            "nns_c{broken 1\n"
            "nns_d{q=\"0.99\"} 7\n")
        assert flat['nns_a{l="x y"}'] == 12.0
        assert flat["nns_b"] == 3.5
        assert flat['nns_d{q="0.99"}'] == 7.0
        assert not any("broken" in k for k in flat)

    def test_label_escape_round_trip(self):
        """metrics.py escapes, dashboard.py decodes: values with
        backslash-n sequences must round-trip exactly (sequential
        replaces would turn an escaped backslash + 'n' into a
        newline)."""
        from nnstreamer_tpu.obs.dashboard import key_labels
        from nnstreamer_tpu.obs.metrics import _label_str

        for value in ('C:\\network', 'a"b', "line\nbreak",
                      "\\\\n", "plain"):
            key = "nns_x" + _label_str({"p": value})
            assert key_labels(key)["p"] == value, value

    def test_scrape_source_appends_metrics_path_to_full_urls(self):
        from nnstreamer_tpu.obs.dashboard import ScrapeSource

        assert ScrapeSource("127.0.0.1:9090").url \
            == "http://127.0.0.1:9090/metrics"
        assert ScrapeSource("http://h:9").url == "http://h:9/metrics"
        assert ScrapeSource("http://h:9/").url == "http://h:9/metrics"
        assert ScrapeSource("http://h:9/custom").url \
            == "http://h:9/custom"
