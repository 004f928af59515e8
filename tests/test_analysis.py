"""``ldt check`` analyzer tests: per-rule true-positive/true-negative
fixtures, suppression comments, baseline behavior, JSON schema, CLI
dispatch, and the self-check that the repo itself is clean."""

import io
import json
import os
import textwrap
import time
from pathlib import Path

import pytest

from lance_distributed_training_tpu.analysis import (
    CheckConfig,
    analyze,
    all_rules,
    check_main,
)

pytestmark = pytest.mark.fast

REPO_ROOT = Path(__file__).resolve().parents[1]


def run_rules(tmp_path, files, **config_kwargs):
    """Write fixture ``files`` ({relpath: source}) under tmp_path and run
    the analyzer over them. Returns the finding list."""
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    config_kwargs.setdefault("paths", ["."])
    config_kwargs.setdefault("queue_paths", ["*"])
    config = CheckConfig(**config_kwargs)
    return analyze(str(tmp_path), config)


def rule_ids(findings):
    return [f.rule for f in findings]


# -- LDT000 ----------------------------------------------------------------


def test_syntax_error_is_a_finding(tmp_path):
    findings = run_rules(tmp_path, {"bad.py": "def broken(:\n"})
    assert rule_ids(findings) == ["LDT000"]


# -- LDT001 unseeded global RNG --------------------------------------------


def test_ldt001_flags_np_global_state(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import numpy as np
        order = np.random.permutation(100)
    """})
    assert rule_ids(findings) == ["LDT001"]
    assert "default_rng" in findings[0].message


def test_ldt001_flags_stdlib_random(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import random
        random.shuffle([1, 2, 3])
    """})
    assert rule_ids(findings) == ["LDT001"]


def test_ldt001_accepts_seeded_generator(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import numpy as np
        rng = np.random.default_rng(7)
        order = rng.permutation(100)
    """})
    assert findings == []


# -- LDT002 wall-clock seed ------------------------------------------------


def test_ldt002_flags_time_assigned_to_seed(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import time
        seed = int(time.time())
    """})
    assert rule_ids(findings) == ["LDT002"]


def test_ldt002_flags_time_as_seed_keyword(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import time

        def build(make_plan):
            return make_plan(8, seed=time.time_ns())
    """})
    assert rule_ids(findings) == ["LDT002"]


def test_ldt002_accepts_timing_use(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import time
        t0 = time.time()
        elapsed = time.time() - t0
    """})
    assert findings == []


# -- LDT003 unsorted fs listing --------------------------------------------


def test_ldt003_flags_bare_listdir(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import os

        def samples(root):
            out = []
            for name in os.listdir(root):
                out.append(name)
            return out
    """})
    assert rule_ids(findings) == ["LDT003"]


def test_ldt003_accepts_sorted_and_orderless_uses(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import os

        def classes(root):
            names = sorted(d for d in os.listdir(root))
            direct = sorted(os.listdir(root))
            count = len(os.listdir(root))
            present = "x" in os.listdir(root)
            later = os.listdir(root)
            later.sort()
            return names, direct, count, present, later
    """})
    assert findings == []


# -- LDT101 / LDT102 jit purity --------------------------------------------


def test_ldt101_flags_print_in_decorated_jit(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import jax

        @jax.jit
        def step(x):
            print("loss", x)
            return x * 2
    """})
    assert rule_ids(findings) == ["LDT101"]


def test_ldt101_flags_wrapped_function_by_name(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import jax
        import logging

        def step(x):
            logging.info("tracing %s", x)
            return x

        fast_step = jax.jit(step, donate_argnums=(0,))
    """})
    assert rule_ids(findings) == ["LDT101"]


def test_ldt102_flags_host_syncs_in_jit(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def step(x, n):
            scale = float(x)
            return x.item() + scale
    """})
    assert sorted(rule_ids(findings)) == ["LDT102", "LDT102"]


def test_ldt101_log_named_math_variable_is_not_telemetry(tmp_path):
    # `log = jnp.log(p); log.sum()` is math — only logging VERBS on a
    # logger-named variable count as side effects.
    findings = run_rules(tmp_path, {"m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(p, y):
            log = jnp.log(p)
            return -(log * y).sum()

        @jax.jit
        def bad(logger, x):
            logger.info("x=%s", x)
            return x
    """})
    assert rule_ids(findings) == ["LDT101"]
    assert "logger.info" in findings[0].message


def test_jit_purity_accepts_clean_step_and_outside_effects(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(state, batch):
            loss = jnp.mean(batch)
            return state, loss

        def outer(batch):
            loss = step(None, batch)[1]
            print("loss", float(loss))  # outside jit: fine
            return loss.item()
    """})
    assert findings == []


# -- LDT201 thread lifecycle -----------------------------------------------


def test_ldt201_flags_thread_without_policy(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        def go(fn):
            t = threading.Thread(target=fn)
            t.start()
    """})
    # Both layers fire: the per-module policy rule (no daemon, no join)
    # and the r11 ownership dataflow (a joinable thread held at fall-off).
    assert sorted(rule_ids(findings)) == ["LDT1201", "LDT201"]


def test_ldt201_accepts_daemon_or_join(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        def daemonized(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()

        def joined(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
    """})
    assert findings == []


# -- LDT202 unbounded queue ------------------------------------------------


def test_ldt202_flags_unbounded_queue_on_stream_path(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import queue
        q = queue.Queue()
    """})
    assert rule_ids(findings) == ["LDT202"]


def test_ldt202_flags_maxsize_zero_as_unbounded(tmp_path):
    # Stdlib semantics: maxsize<=0 means INFINITE — the explicit-default
    # spelling must not slip past the gate.
    findings = run_rules(tmp_path, {"m.py": """\
        import queue
        a = queue.Queue(maxsize=0)
        b = queue.Queue(0)
        c = queue.Queue(-1)
    """})
    assert rule_ids(findings) == ["LDT202", "LDT202", "LDT202"]


def test_ldt202_accepts_bounded_and_out_of_scope(tmp_path):
    findings = run_rules(
        tmp_path,
        {
            "svc/stream.py": "import queue\nq = queue.Queue(maxsize=4)\n",
            "tools/misc.py": "import queue\nq = queue.Queue()\n",
        },
        queue_paths=["svc/*"],
    )
    assert findings == []


# -- LDT203 handshake recv timeout ------------------------------------------


def test_ldt203_flags_handshake_recv_without_deadline(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        def do_handshake(sock):
            hello = sock.recv(64)
            return hello
    """})
    assert rule_ids(findings) == ["LDT203"]


def test_ldt203_accepts_deadline_before_recv(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        def do_handshake(sock):
            sock.settimeout(30.0)
            hello = sock.recv(64)
            sock.settimeout(None)
            return hello

        def stream_loop(sock):
            # steady-state receive: not handshake-shaped, no deadline needed
            return sock.recv(64)
    """})
    assert findings == []


def test_ldt203_accepts_deadline_kwarg(tmp_path):
    # recv_msg(sock, deadline=...) bounds the whole frame read — stronger
    # than settimeout; deadline=None does not count.
    findings = run_rules(tmp_path, {"m.py": """\
        def handshake_ok(sock, recv_msg, now):
            return recv_msg(sock, deadline=now() + 30.0)

        def handshake_bad(sock, recv_msg):
            return recv_msg(sock, deadline=None)
    """})
    assert rule_ids(findings) == ["LDT203"]
    assert findings[0].line == 5


# -- LDT301 resource ownership ----------------------------------------------


def test_ldt301_flags_self_store_without_teardown(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        class Logger:
            def __init__(self, path):
                self._f = open(path, "a")

            def log(self, line):
                self._f.write(line)
    """})
    assert rule_ids(findings) == ["LDT301"]
    assert "Logger" in findings[0].message


def test_ldt301_flags_discarded_and_never_closed(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import socket

        def probe(path, addr):
            open(path)
            s = socket.socket()
            s.connect(addr)
    """})
    # The discarded open() and the never-closed socket each trip LDT301;
    # the r11 ownership dataflow (LDT1201) also sees the socket held at
    # every exit of probe().
    assert sorted(rule_ids(findings)) == ["LDT1201", "LDT301", "LDT301"]


def test_ldt301_accepts_ownership_stories(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import socket

        class Service:
            def __init__(self, path):
                self._f = open(path, "a")

            def close(self):
                self._f.close()

        def read(path):
            with open(path) as f:
                return f.read()

        def dial(addr):
            s = socket.socket()
            try:
                s.connect(addr)
                return s
            except BaseException:
                # BaseException, not OSError: the r11 ownership dataflow
                # (LDT1201) correctly treats a typed handler as letting
                # other exception classes escape with the fd open — the
                # balancer fd-leak class.
                s.close()
                raise

        def handoff(addr, register):
            s = socket.socket()
            register(s)
    """})
    assert findings == []


# -- LDT501 protocol consistency --------------------------------------------


def test_ldt501_flags_missing_and_mismatched_constants(tmp_path):
    findings = run_rules(
        tmp_path,
        {
            "svc/__init__.py": "",
            "svc/protocol.py": "PROTOCOL_VERSION = 1\nMSG_HELLO = 1\n",
            "svc/client.py": """\
                from . import protocol as P

                MSG_HELLO = 2

                def hello():
                    return P.MSG_HELLO_OK, P.PROTOCOL_VERSION
            """,
        },
        protocol_module="svc/protocol.py",
    )
    assert sorted(rule_ids(findings)) == ["LDT501", "LDT501"]
    messages = " | ".join(f.message for f in findings)
    assert "MSG_HELLO_OK" in messages  # referenced but undefined
    assert "redefined" in messages  # MSG_HELLO = 2 vs 1


def test_ldt501_checks_package_init_imports(tmp_path):
    # Relative imports in an __init__.py resolve against the package
    # itself, not its parent — a missing constant re-exported from
    # svc/__init__.py must be caught.
    findings = run_rules(
        tmp_path,
        {
            "svc/__init__.py": "from .protocol import MSG_GONE\n",
            "svc/protocol.py": "MSG_HELLO = 1\n",
        },
        protocol_module="svc/protocol.py",
    )
    assert rule_ids(findings) == ["LDT501"]
    assert "MSG_GONE" in findings[0].message


def test_ldt501_sees_annotated_constants(tmp_path):
    # `MSG_FOO: int = 7` (AnnAssign) must count as defined — and a
    # mismatched annotated redefinition must still be caught.
    findings = run_rules(
        tmp_path,
        {
            "svc/__init__.py": "",
            "svc/protocol.py": "MSG_FOO: int = 7\n",
            "svc/client.py": """\
                from . import protocol as P

                MSG_FOO: int = 8

                def use():
                    return P.MSG_FOO
            """,
        },
        protocol_module="svc/protocol.py",
    )
    assert rule_ids(findings) == ["LDT501"]
    assert "redefined" in findings[0].message


def test_ldt501_accepts_consistent_references(tmp_path):
    findings = run_rules(
        tmp_path,
        {
            "svc/__init__.py": "",
            "svc/protocol.py": "PROTOCOL_VERSION = 1\nMSG_HELLO = 1\n",
            "svc/client.py": """\
                from . import protocol as P

                def hello():
                    return P.MSG_HELLO, P.PROTOCOL_VERSION
            """,
        },
        protocol_module="svc/protocol.py",
    )
    assert findings == []


def test_real_protocol_constants_all_resolve():
    # The live client/server must only reference constants protocol.py
    # defines — the exact invariant LDT501 encodes, asserted directly
    # against the real modules as a belt-and-braces check.
    import lance_distributed_training_tpu.service.protocol as P

    for name in ("MSG_HELLO", "MSG_HELLO_OK", "MSG_BATCH", "MSG_ACK",
                 "MSG_END", "MSG_ERROR", "PROTOCOL_VERSION"):
        assert hasattr(P, name)


# -- LDT601 obs hygiene ------------------------------------------------------


def test_ldt601_flags_wall_clock_in_instrumented_module(tmp_path):
    findings = run_rules(
        tmp_path,
        {"obs/timer.py": """\
            import time

            def measure(fn):
                t0 = time.time()
                fn()
                return time.time() - t0
        """},
        obs_paths=["obs/*"],
    )
    assert rule_ids(findings) == ["LDT601", "LDT601"]
    assert "monotonic" in findings[0].message


def test_ldt601_accepts_monotonic_clocks_and_epoch_stamps(tmp_path):
    findings = run_rules(
        tmp_path,
        {"obs/timer.py": """\
            import time

            def measure(fn):
                t0 = time.perf_counter()
                fn()
                return time.perf_counter() - t0

            def stamp():
                # epoch stamp for cross-process lineage: sanctioned
                return {"created_ns": time.time_ns(),
                        "mono": time.monotonic_ns()}
        """},
        obs_paths=["obs/*"],
    )
    assert findings == []


def test_ldt601_ignores_uninstrumented_modules(tmp_path):
    findings = run_rules(
        tmp_path,
        {"elsewhere.py": """\
            import time
            started_at = time.time()
        """},
        obs_paths=["obs/*"],
    )
    assert findings == []


def test_ldt601_flags_invalid_metric_name(tmp_path):
    findings = run_rules(
        tmp_path,
        {"obs/meter.py": """\
            def wire(registry):
                registry.counter("svc_batches_sent").inc()
                registry.histogram("wire_ms").observe(1.0)
                registry.gauge("Queue-Depth").set(3)
                registry.counter(name="9starts_with_digit").inc()
        """},
        obs_paths=["obs/*"],
    )
    assert rule_ids(findings) == ["LDT601", "LDT601"]
    assert "Prometheus" in findings[0].message


def test_ldt601_dynamic_names_not_flagged(tmp_path):
    # Computed names (f-strings, variables) are validated at runtime by the
    # registry itself; the static rule only judges literals.
    findings = run_rules(
        tmp_path,
        {"obs/meter.py": """\
            def wire(registry, prefix, key):
                registry.counter(f"{prefix}_{key}").inc()
        """},
        obs_paths=["obs/*"],
    )
    assert findings == []


def test_ldt601_suppression(tmp_path):
    findings = run_rules(
        tmp_path,
        {"obs/t.py": """\
            import time
            t = time.time()  # ldt: ignore[LDT601]
        """},
        obs_paths=["obs/*"],
    )
    assert findings == []


# -- LDT701 copy hygiene -----------------------------------------------------


def test_ldt701_flags_materializing_calls_on_hot_paths(tmp_path):
    findings = run_rules(
        tmp_path,
        {"data/decode.py": """\
            def slow(col, view, off, n):
                rows = col.to_pylist()
                blob = col.to_pybytes()
                meta = bytes(view[off : off + n])
                alt = bytes(view.tobytes())
                return rows, blob, meta, alt
        """},
        hot_paths=["data/*"],
    )
    assert rule_ids(findings) == ["LDT701"] * 4
    assert "hot path" in findings[0].message


def test_ldt701_accepts_buffer_passthrough_and_benign_bytes(tmp_path):
    findings = run_rules(
        tmp_path,
        {"data/decode.py": """\
            import numpy as np

            def fast(col, payload, n):
                buffers = col.buffers()
                arr = np.frombuffer(memoryview(payload), dtype=np.uint8)
                pad = bytes(n)          # int arg: allocation, not a copy
                raw = bytes(payload)    # name arg: stays legal
                return buffers, arr, pad, raw
        """},
        hot_paths=["data/*"],
    )
    assert findings == []


def test_ldt701_ignores_cold_modules(tmp_path):
    findings = run_rules(
        tmp_path,
        {"tools/report.py": """\
            def dump(col):
                return col.to_pylist()
        """},
        hot_paths=["data/*"],
    )
    assert findings == []


def test_ldt701_repo_hot_paths_are_clean_and_baseline_is_empty():
    """The real tree: zero LDT701 findings — the two deliberate fallbacks
    (the PIL decode arm in data/decode.py, the small JSON control-meta
    copy in service/protocol.py) carry reason-required inline ignores at
    the site, so the committed baseline is empty and MUST stay empty (a
    new materialisation fails `ldt check` directly, with no grandfather
    pool to hide in)."""
    import os

    from lance_distributed_training_tpu.analysis.config import load_config
    from lance_distributed_training_tpu.analysis.core import analyze_project

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_config(root)
    findings, _modules, _ = analyze_project(root, config)
    ldt701 = [f for f in findings if f.rule == "LDT701"]
    assert ldt701 == [], [f.location() for f in ldt701]
    baseline = json.loads(
        (REPO_ROOT / ".ldt-baseline.json").read_text()
    )
    assert baseline == {"version": 1, "findings": []}, (
        "the baseline must stay empty: fix new findings or add a "
        "reason-required inline ignore, never re-grandfather"
    )


# -- LDT801 placement hygiene ------------------------------------------------


def test_ldt801_flags_direct_h2d_calls_on_hot_paths(tmp_path):
    findings = run_rules(
        tmp_path,
        {"data/loader.py": """\
            import jax
            from jax import device_put

            def place(batch, sharding, shards):
                a = jax.device_put(batch, sharding)
                b = device_put(batch, sharding)
                c = jax.make_array_from_single_device_arrays(
                    (8,), sharding, shards
                )
                d = jax.make_array_from_process_local_data(sharding, batch)
                return a, b, c, d
        """},
        hot_paths=["data/*"],
    )
    ldt801 = [f for f in findings if f.rule == "LDT801"]
    assert len(ldt801) == 4, [f.message for f in findings]
    assert "placement plane" in ldt801[0].message


def test_ldt801_accepts_compat_routed_calls(tmp_path):
    findings = run_rules(
        tmp_path,
        {"data/loader.py": """\
            from parallel._compat import (
                device_put,
                make_array_from_single_device_arrays,
            )

            def place(batch, sharding, shards):
                a = device_put(batch, sharding)
                b = make_array_from_single_device_arrays(
                    (8,), sharding, shards
                )
                return a, b
        """},
        hot_paths=["data/*"],
    )
    assert [f for f in findings if f.rule == "LDT801"] == []


def test_ldt801_exempts_the_placement_plane_itself(tmp_path):
    findings = run_rules(
        tmp_path,
        {"data/placement.py": """\
            import jax

            def place(batch, sharding):
                return jax.device_put(batch, sharding)
        """},
        hot_paths=["data/*"],
    )
    assert [f for f in findings if f.rule == "LDT801"] == []


def test_ldt801_ignores_cold_modules(tmp_path):
    findings = run_rules(
        tmp_path,
        {"tools/restore.py": """\
            import jax

            def commit(tree, shardings):
                return jax.device_put(tree, shardings)
        """},
        hot_paths=["data/*"],
    )
    assert [f for f in findings if f.rule == "LDT801"] == []


def test_ldt801_repo_hot_paths_are_clean():
    """The real tree: the shipped hot-path modules route every H2D call
    through data/placement.py or parallel/_compat.py — zero LDT801
    findings, no baseline entries needed."""
    import os

    from lance_distributed_training_tpu.analysis.config import load_config
    from lance_distributed_training_tpu.analysis.core import analyze_project

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_config(root)
    findings, _, _ = analyze_project(root, config)
    assert [f.location() for f in findings if f.rule == "LDT801"] == []


# -- suppressions ------------------------------------------------------------


def test_suppression_comment_silences_matching_rule(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import numpy as np
        a = np.random.permutation(10)  # ldt: ignore[LDT001]
        b = np.random.permutation(10)  # ldt: ignore
        c = np.random.permutation(10)  # ldt: ignore[LDT999]
        d = np.random.permutation(10)
    """})
    assert [f.line for f in findings] == [4, 5]  # c (wrong id) and d


# -- baseline ----------------------------------------------------------------


VIOLATION = "import numpy as np\nx = np.random.permutation(4)\n"


def _write_pkg(tmp_path, source=VIOLATION):
    (tmp_path / "m.py").write_text(source)


def test_baseline_grandfathers_then_catches_new(tmp_path):
    pytest.importorskip("tomli")
    # Baseline updates require the configured full scan (not positional
    # paths), so configure the fixture root via pyproject.
    (tmp_path / "pyproject.toml").write_text(
        '[tool.ldt-check]\npaths = ["."]\n'
    )
    _write_pkg(tmp_path)
    root = str(tmp_path)
    out = io.StringIO()
    assert check_main(["--root", root], out=out) == 1  # dirty, no baseline

    assert check_main(["--root", root, "--update-baseline"], out=out) == 0
    assert (tmp_path / ".ldt-baseline.json").exists()
    assert check_main(["--root", root], out=out) == 0  # grandfathered

    # Line drift must not un-grandfather: shift the violation down.
    _write_pkg(tmp_path, "# a leading comment\n" + VIOLATION)
    assert check_main(["--root", root, "."], out=out) == 0

    # A NEW violation still fails, and only the new one is reported.
    _write_pkg(tmp_path, VIOLATION + "import random\nrandom.shuffle([1])\n")
    out = io.StringIO()
    assert check_main(["--root", root, "."], out=out) == 1
    assert "LDT001" in out.getvalue()
    text = out.getvalue()
    assert "1 new finding" in text and "1 baselined" in text

    # --no-baseline reports everything.
    out = io.StringIO()
    assert check_main(["--root", root, ".", "--no-baseline"], out=out) == 1
    assert "2 new findings" in out.getvalue()


def test_update_baseline_refuses_partial_scan(tmp_path):
    _write_pkg(tmp_path)
    out = io.StringIO()
    rc = check_main(
        ["--root", str(tmp_path), ".", "--update-baseline"], out=out
    )
    assert rc == 2
    assert "full scan" in out.getvalue()


def test_zero_files_scanned_is_an_error_not_a_pass(tmp_path):
    # Wrong cwd / bad --root must not produce a silent "clean" gate pass.
    out = io.StringIO()
    rc = check_main(["--root", str(tmp_path), "no/such/dir"], out=out)
    assert rc == 2
    assert "no files matched" in out.getvalue()


# -- JSON reporter -----------------------------------------------------------


def test_json_output_schema(tmp_path):
    _write_pkg(tmp_path)
    out = io.StringIO()
    rc = check_main(["--root", str(tmp_path), ".", "--json"], out=out)
    assert rc == 1
    data = json.loads(out.getvalue())
    assert data["version"] == 2
    assert data["clean"] is False
    assert isinstance(data["files_checked"], int)
    assert isinstance(data["grandfathered"], int)
    # r9 additions: analysis wall time (the parse-once satellite's receipt)
    # rides every JSON report.
    assert isinstance(data["wall_time_ms"], (int, float))
    assert isinstance(data["parse_ms"], (int, float))
    assert data["wall_time_ms"] >= data["parse_ms"] >= 0
    (finding,) = data["findings"]
    assert set(finding) == {
        "rule", "rule_family", "path", "line", "col", "message",
        "fingerprint", "witness_pruned",
    }
    assert finding["rule"] == "LDT001"
    assert finding["rule_family"] == "determinism"
    assert finding["witness_pruned"] is False
    assert finding["path"] == "m.py"
    assert finding["line"] == 2
    assert isinstance(finding["fingerprint"], str) and finding["fingerprint"]


def test_json_clean_output(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    out = io.StringIO()
    rc = check_main(["--root", str(tmp_path), ".", "--json"], out=out)
    assert rc == 0
    data = json.loads(out.getvalue())
    assert data["clean"] is True and data["findings"] == []


# -- config ------------------------------------------------------------------


def test_pyproject_config_section(tmp_path):
    pytest.importorskip("tomli")
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""\
        [tool.ldt-check]
        paths = ["pkg"]
        disable = ["ldt001"]
        baseline = "custom-baseline.json"
    """))
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "m.py").write_text(VIOLATION)
    (tmp_path / "outside.py").write_text(VIOLATION)
    out = io.StringIO()
    # LDT001 disabled + paths limited to pkg/ => clean.
    assert check_main(["--root", str(tmp_path)], out=out) == 0

    from lance_distributed_training_tpu.analysis import load_config

    config = load_config(str(tmp_path))
    assert config.paths == ["pkg"]
    assert config.disable == ["LDT001"]
    assert config.baseline == "custom-baseline.json"


# -- CLI dispatch ------------------------------------------------------------


def test_ldt_check_subcommand_dispatch(tmp_path):
    import lance_distributed_training_tpu.cli as cli

    _write_pkg(tmp_path)
    rc = cli.main(["check", "--root", str(tmp_path), ".", "--no-baseline"])
    assert rc == 1

    (tmp_path / "m.py").write_text("x = 1\n")
    rc = cli.main(["check", "--root", str(tmp_path), "."])
    assert rc == 0


def test_list_rules_covers_registry(capsys):
    assert check_main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rid in all_rules():
        assert rid in listed
    assert len(all_rules()) >= 8


# -- LDT901 crash-consistent state writes ------------------------------------


def test_ldt901_flags_inplace_state_write(tmp_path):
    findings = run_rules(tmp_path, {"ckpt.py": """\
        import json

        def save(path, payload):
            with open(path, "w") as f:
                json.dump(payload, f)
    """}, state_paths=["ckpt.py"])
    assert "LDT901" in rule_ids(findings)
    assert "os.replace" in findings[0].message


def test_ldt901_flags_path_write_text(tmp_path):
    findings = run_rules(tmp_path, {"ckpt.py": """\
        from pathlib import Path

        def save(path, payload):
            Path(path).write_text(payload)
    """}, state_paths=["ckpt.py"])
    assert "LDT901" in rule_ids(findings)


def test_ldt901_tempfile_replace_pattern_clean(tmp_path):
    findings = run_rules(tmp_path, {"ckpt.py": """\
        import json
        import os
        import tempfile

        def save(path, payload):
            fd, tmp = tempfile.mkstemp(dir=".")
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
    """}, state_paths=["ckpt.py"])
    assert [f for f in findings if f.rule == "LDT901"] == []


def test_ldt901_append_and_read_modes_exempt(tmp_path):
    findings = run_rules(tmp_path, {"ckpt.py": """\
        def log(path, line):
            with open(path, "a") as f:
                f.write(line)

        def load(path):
            with open(path) as f:
                return f.read()
    """}, state_paths=["ckpt.py"])
    assert [f for f in findings if f.rule == "LDT901"] == []


def test_ldt901_only_in_state_paths(tmp_path):
    findings = run_rules(tmp_path, {"other.py": """\
        def save(path, payload):
            with open(path, "w") as f:
                f.write(payload)
    """}, state_paths=["ckpt.py"])
    assert [f for f in findings if f.rule == "LDT901"] == []


def test_ldt901_repo_state_modules_clean():
    """checkpoint.py and the baseline writer persist state atomically —
    zero LDT901 findings on the repo's own configured state-paths."""
    from lance_distributed_training_tpu.analysis.config import load_config
    from lance_distributed_training_tpu.analysis.core import analyze_project

    root = str(REPO_ROOT)
    config = load_config(root)
    findings, _, _ = analyze_project(root, config)
    assert [f.location() for f in findings if f.rule == "LDT901"] == []


# -- self-check ---------------------------------------------------------------


def test_repo_is_clean_under_ldt_check():
    """The permanent gate: the repo's own package must pass its own lint.
    If this fails, either fix the finding or (deliberately, reviewed)
    suppress/baseline it."""
    out = io.StringIO()
    rc = check_main(["--root", str(REPO_ROOT)], out=out)
    assert rc == 0, f"ldt check found new violations:\n{out.getvalue()}"


# -- LDT1001 lock-order cycles (cross-module concurrency model) ---------------


FIXTURE_ROOT = REPO_ROOT / "tests" / "fixtures" / "concmodel"


def _concmodel_config(**kwargs):
    from lance_distributed_training_tpu.analysis import CheckConfig

    kwargs.setdefault("paths", ["pkg"])
    kwargs.setdefault("queue_paths", ["*"])
    kwargs.setdefault("protocol_module", "pkg/protocol.py")
    kwargs.setdefault("dispatch", {"pkg/alpha.py": ["MSG_PING", "MSG_PONG"]})
    return CheckConfig(**kwargs)


def test_ldt1001_flags_cross_module_cycle(tmp_path):
    findings = run_rules(tmp_path, {
        "a.py": """\
            import threading

            from b import B

            class A:
                def __init__(self, b: "B"):
                    self._la = threading.Lock()
                    self.b = b

                def one(self):
                    with self._la:
                        self.b.two()

                def entered(self):
                    with self._la:
                        return 1
        """,
        "b.py": """\
            import threading

            class B:
                def __init__(self, a: "A"):
                    self._lb = threading.Lock()
                    self.a = a

                def two(self):
                    with self._lb:
                        return 1

                def back(self):
                    with self._lb:
                        self.a.entered()
        """,
    })
    cycles = [f for f in findings if f.rule == "LDT1001"]
    assert len(cycles) == 1, [f.message for f in findings]
    assert "lock-order cycle" in cycles[0].message
    assert "_la" in cycles[0].message and "_lb" in cycles[0].message


def test_ldt1001_consistent_order_is_clean(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class M:
            def __init__(self):
                self._outer = threading.Lock()
                self._inner = threading.Lock()

            def one(self):
                with self._outer:
                    with self._inner:
                        return 1

            def two(self):
                with self._outer:
                    with self._inner:
                        return 2
    """})
    assert [f for f in findings if f.rule == "LDT1001"] == []


def test_ldt1001_multi_item_with_orders_left_to_right(tmp_path):
    # `with a, b:` IS `with a: with b:` — inverted multi-item withs are
    # the same textbook deadlock and must not hide in one statement.
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class M:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a, self._b:
                    return 1

            def two(self):
                with self._b, self._a:
                    return 2
    """})
    cycles = [f for f in findings if f.rule == "LDT1001"]
    assert len(cycles) == 1, [f.message for f in findings]
    assert "lock-order cycle" in cycles[0].message


def test_ldt1001_flags_nonreentrant_self_deadlock(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    return 1
    """})
    selfs = [f for f in findings if f.rule == "LDT1001"]
    assert len(selfs) == 1
    assert "acquired while already held" in selfs[0].message


def test_ldt1001_rlock_reentry_is_clean(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    return 1
    """})
    assert [f for f in findings if f.rule == "LDT1001"] == []


# -- LDT1002 unsynchronized shared state --------------------------------------


def test_ldt1002_flags_cross_thread_unlocked_attr(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class Worker:
            def __init__(self):
                self.value = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self.value = self.value + 1

            def read(self):
                return self.value
    """})
    races = [f for f in findings if f.rule == "LDT1002"]
    assert len(races) == 1, [f.message for f in findings]
    assert "Worker.value" in races[0].message
    assert races[0].line == 11  # the write site, not the read


def test_ldt1002_common_lock_is_clean(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.value = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self._lock:
                    self.value = self.value + 1

            def read(self):
                with self._lock:
                    return self.value
    """})
    assert [f for f in findings if f.rule == "LDT1002"] == []


def test_ldt1002_locked_suffix_convention_is_computed(tmp_path):
    # _bump_locked never takes the lock itself; every call site holds it.
    # The held-at-entry fixpoint must prove that instead of trusting names.
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.value = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self._lock:
                    self._bump_locked()

            def _bump_locked(self):
                self.value = self.value + 1

            def read(self):
                with self._lock:
                    return self.value
    """})
    assert [f for f in findings if f.rule == "LDT1002"] == []


def test_ldt1002_threadsafe_type_handoff_is_clean(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class Worker:
            def __init__(self):
                self.done = threading.Event()

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self.done = threading.Event()  # reassigned, but an Event

            def wait(self):
                return self.done.wait(1.0)
    """})
    assert [f for f in findings if f.rule == "LDT1002"] == []


def test_ldt1002_prespawn_publication_is_clean(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import threading

        class Worker:
            def __init__(self):
                self.ready = 0

            def start(self):
                self.ready = 1
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                return self.ready
    """})
    assert [f for f in findings if f.rule == "LDT1002"] == []


def test_ldt10xx_ignore_requires_reason(tmp_path):
    racy = """\
        import threading

        class Worker:
            def __init__(self):
                self.value = 0

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self.value = 1{comment}

            def read(self):
                return self.value
    """
    # Bare ignore: stays live (the gate still fails).
    findings = run_rules(
        tmp_path / "bare",
        {"m.py": racy.format(comment="  # ldt: ignore[LDT1002]")},
    )
    assert [f.rule for f in findings if f.rule == "LDT1002"] == ["LDT1002"]
    # Suppress-all bare ignore: also stays live for LDT10xx.
    findings = run_rules(
        tmp_path / "all",
        {"m.py": racy.format(comment="  # ldt: ignore")},
    )
    assert [f.rule for f in findings if f.rule == "LDT1002"] == ["LDT1002"]
    # Reasoned ignore: suppressed.
    findings = run_rules(
        tmp_path / "reasoned",
        {"m.py": racy.format(
            comment="  # ldt: ignore[LDT1002] -- benign monotonic flag"
        )},
    )
    assert [f for f in findings if f.rule == "LDT1002"] == []
    # Non-10xx rules keep the old contract: bare ignores still work.
    findings = run_rules(
        tmp_path / "old",
        {"m.py": "import numpy as np\n"
                 "x = np.random.permutation(4)  # ldt: ignore[LDT001]\n"},
    )
    assert findings == []


# -- LDT1003 dispatcher exhaustiveness ----------------------------------------


_PROTO_AB = "MSG_A = 1\nMSG_B = 2\n"


def test_ldt1003_flags_missing_dispatch_arm(tmp_path):
    findings = run_rules(
        tmp_path,
        {
            "proto.py": _PROTO_AB,
            "d.py": """\
                import proto

                def handle(msg_type):
                    if msg_type == proto.MSG_A:
                        return "a"
                    raise ValueError(msg_type)
            """,
        },
        protocol_module="proto.py",
        dispatch={"d.py": ["MSG_A", "MSG_B"]},
    )
    hits = [f for f in findings if f.rule == "LDT1003"]
    assert len(hits) == 1
    assert "MSG_B" in hits[0].message and hits[0].path == "d.py"


def test_ldt1003_flags_orphan_constant_at_definition(tmp_path):
    findings = run_rules(
        tmp_path,
        {
            "proto.py": _PROTO_AB,
            "d.py": """\
                import proto

                def handle(msg_type):
                    if msg_type == proto.MSG_A:
                        return "a"
                    raise ValueError(msg_type)
            """,
        },
        protocol_module="proto.py",
        dispatch={"d.py": ["MSG_A"]},
    )
    hits = [f for f in findings if f.rule == "LDT1003"]
    assert len(hits) == 1
    assert "MSG_B" in hits[0].message
    assert hits[0].path == "proto.py" and hits[0].line == 2


def test_ldt1003_flags_config_drift(tmp_path):
    findings = run_rules(
        tmp_path,
        {
            "proto.py": "MSG_A = 1\n",
            "d.py": """\
                import proto

                def handle(msg_type):
                    if msg_type == proto.MSG_A:
                        return "a"
            """,
        },
        protocol_module="proto.py",
        dispatch={"d.py": ["MSG_A", "MSG_NOPE"]},
    )
    hits = [f for f in findings if f.rule == "LDT1003"]
    assert len(hits) == 1
    assert "MSG_NOPE" in hits[0].message and "drift" in hits[0].message


def test_ldt1003_dict_dispatch_and_compare_are_coverage(tmp_path):
    findings = run_rules(
        tmp_path,
        {
            "proto.py": _PROTO_AB + "MSG_C = 3\n",
            "d.py": """\
                import proto

                def handle(msg_type, req):
                    handler = {
                        proto.MSG_A: handle_a,
                        proto.MSG_B: handle_b,
                    }.get(msg_type)
                    if msg_type == proto.MSG_C:
                        raise ValueError("explicitly rejected")
                    return handler(req)

                def handle_a(req):
                    return "a"

                def handle_b(req):
                    return "b"
            """,
        },
        protocol_module="proto.py",
        dispatch={"d.py": ["MSG_A", "MSG_B", "MSG_C"]},
    )
    assert [f for f in findings if f.rule == "LDT1003"] == []


def test_ldt1003_inert_without_scanned_dispatchers(tmp_path):
    # A fixture tree whose configured dispatcher modules are not in the
    # scan (the LDT501 fixtures, most third-party layouts) must not fail
    # the orphan-constant check.
    findings = run_rules(
        tmp_path,
        {"proto.py": "MSG_LONELY = 9\n"},
        protocol_module="proto.py",
        dispatch={"not/scanned.py": ["MSG_LONELY"]},
    )
    assert [f for f in findings if f.rule == "LDT1003"] == []


# -- LDT1101 tunable bounds ---------------------------------------------------


def test_ldt1101_flags_missing_bounds(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        from lance_distributed_training_tpu.tune.tunable import Tunable

        def register(obj):
            return Tunable("workers", obj.get, obj.set)
    """})
    hits = [f for f in findings if f.rule == "LDT1101"]
    assert len(hits) == 1
    assert "hi/lo" in hits[0].message


def test_ldt1101_flags_one_missing_bound(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        from lance_distributed_training_tpu.tune import Tunable

        def register(obj):
            return Tunable("workers", obj.get, obj.set, lo=1)
    """})
    hits = [f for f in findings if f.rule == "LDT1101"]
    assert len(hits) == 1 and "hi=" in hits[0].message


def test_ldt1101_flags_degenerate_literal_range(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        from lance_distributed_training_tpu.tune.tunable import Tunable

        def register(obj):
            return Tunable("workers", obj.get, obj.set, lo=8, hi=8)
    """})
    hits = [f for f in findings if f.rule == "LDT1101"]
    assert len(hits) == 1 and "degenerate" in hits[0].message


def test_ldt1101_accepts_bounded_and_splat(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        from lance_distributed_training_tpu.tune.tunable import Tunable

        def good(obj):
            return Tunable("workers", obj.get, obj.set, lo=1, hi=8)

        def computed(obj, n):
            return Tunable("workers", obj.get, obj.set, lo=1, hi=max(2, n))

        def splat(obj, kw):
            # **kwargs may carry the bounds: benefit of the doubt (the
            # runtime keyword-only signature still backstops it).
            return Tunable("workers", obj.get, obj.set, **kw)
    """})
    assert [f for f in findings if f.rule == "LDT1101"] == []


def test_ldt1101_ignores_unrelated_tunable_names(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        class Other:
            pass

        def make():
            return Other()
    """})
    assert [f for f in findings if f.rule == "LDT1101"] == []


# -- the seeded fixture package ----------------------------------------------


def test_fixture_package_yields_exactly_the_planted_findings():
    from lance_distributed_training_tpu.analysis import analyze

    findings = analyze(str(FIXTURE_ROOT), _concmodel_config())
    assert [(f.rule, f.path) for f in findings] == [
        ("LDT1001", "pkg/alpha.py"),
        ("LDT1002", "pkg/alpha.py"),
        ("LDT1003", "pkg/protocol.py"),
    ], [f.message for f in findings]
    by_rule = {f.rule: f for f in findings}
    assert "Alpha.shared" in by_rule["LDT1002"].message
    assert "MSG_ORPHAN" in by_rule["LDT1003"].message
    assert "_lock_a" in by_rule["LDT1001"].message


def _lock_site(relpath: str, needle: str, absolute: bool = False) -> str:
    path = FIXTURE_ROOT / relpath
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        if needle in line:
            prefix = str(path) if absolute else relpath
            return f"{prefix}:{i}"
    raise AssertionError(f"{needle} not in {relpath}")


def test_witness_prunes_unobserved_cycle_edge():
    from lance_distributed_training_tpu.analysis import analyze

    site_a = _lock_site("pkg/alpha.py", "_lock_a = threading.Lock()")
    site_b = _lock_site("pkg/beta.py", "_lock_b = threading.Lock()")
    config = _concmodel_config()
    # Both locks exercised, only the a->b ordering ever observed: the
    # static b->a edge (Beta.kick is dead code at runtime) is
    # contradicted, so the cycle prunes.
    config.lock_witness = {
        "edges": {(site_a, site_b)},
        "acquired": {site_a: 5, site_b: 5},
    }
    findings = analyze(str(FIXTURE_ROOT), config)
    cycle = next(f for f in findings if f.rule == "LDT1001")
    assert cycle.witness_pruned is True
    assert "witness_pruned" in cycle.message


def test_witness_corroborates_observed_cycle():
    from lance_distributed_training_tpu.analysis import analyze

    site_a = _lock_site("pkg/alpha.py", "_lock_a = threading.Lock()")
    site_b = _lock_site("pkg/beta.py", "_lock_b = threading.Lock()")
    config = _concmodel_config()
    config.lock_witness = {
        "edges": {(site_a, site_b), (site_b, site_a)},
        "acquired": {site_a: 5, site_b: 5},
    }
    findings = analyze(str(FIXTURE_ROOT), config)
    cycle = next(f for f in findings if f.rule == "LDT1001")
    assert cycle.witness_pruned is False
    assert "observed at runtime" in cycle.message


def test_witness_without_exercise_does_not_prune():
    from lance_distributed_training_tpu.analysis import analyze

    site_a = _lock_site("pkg/alpha.py", "_lock_a = threading.Lock()")
    config = _concmodel_config()
    # _lock_b never acquired at runtime: absence of the b->a edge proves
    # nothing, the cycle must stay live.
    config.lock_witness = {"edges": set(), "acquired": {site_a: 5}}
    findings = analyze(str(FIXTURE_ROOT), config)
    cycle = next(f for f in findings if f.rule == "LDT1001")
    assert cycle.witness_pruned is False


def test_check_main_lock_witness_end_to_end(tmp_path):
    pytest.importorskip("tomli")
    witness = {
        "version": 1,
        "edges": [{
            "src": _lock_site(
                "pkg/alpha.py", "_lock_a = threading.Lock()", absolute=True
            ),
            "dst": _lock_site(
                "pkg/beta.py", "_lock_b = threading.Lock()", absolute=True
            ),
            "count": 4,
        }],
        "acquired": {
            _lock_site("pkg/alpha.py", "_lock_a = threading.Lock()",
                       absolute=True): 4,
            _lock_site("pkg/beta.py", "_lock_b = threading.Lock()",
                       absolute=True): 4,
        },
    }
    wpath = tmp_path / "witness.json"
    wpath.write_text(json.dumps(witness))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(FIXTURE_ROOT), "--json", "--no-baseline",
         "--lock-witness", str(wpath)],
        out=out,
    )
    assert rc == 1  # the LDT1002/LDT1003 seeds still fail the gate
    data = json.loads(out.getvalue())
    cycle = next(f for f in data["findings"] if f["rule"] == "LDT1001")
    assert cycle["witness_pruned"] is True
    assert cycle["rule_family"] == "lock-order"
    race = next(f for f in data["findings"] if f["rule"] == "LDT1002")
    assert race["witness_pruned"] is False


# -- ldt graph ----------------------------------------------------------------


def test_graph_dot_smoke():
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(["--root", str(FIXTURE_ROOT), "pkg", "--dot"], out=out)
    assert rc == 0
    dot = out.getvalue()
    assert dot.startswith("digraph ldt_concurrency")
    assert '"thread:pkg.alpha.Alpha._loop"' in dot
    assert '"lock:pkg.alpha.Alpha._lock_a"' in dot
    assert '"lock:pkg.beta.Beta._lock_b"' in dot
    # Both cycle edges render.
    assert ('"lock:pkg.alpha.Alpha._lock_a" -> '
            '"lock:pkg.beta.Beta._lock_b"') in dot
    assert ('"lock:pkg.beta.Beta._lock_b" -> '
            '"lock:pkg.alpha.Alpha._lock_a"') in dot


def test_graph_text_smoke():
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(["--root", str(FIXTURE_ROOT), "pkg"], out=out)
    assert rc == 0
    text = out.getvalue()
    assert "thread Alpha._loop" in text
    assert "lock-order cycles: 1" in text


def test_graph_cli_dispatch():
    import lance_distributed_training_tpu.cli as cli

    rc = cli.main(["graph", "--root", str(FIXTURE_ROOT), "pkg"])
    assert rc == 0


# -- runtime lock sanitizer (utils/lockorder.py) ------------------------------


@pytest.fixture()
def lockorder_sandbox():
    """Snapshot/restore the recorder around tests that install, reset, or
    pollute it: a sanitizer-enabled session (``LDT_LOCK_SANITIZER=1``
    tier-1 run) collects its witness ACROSS the suite, and these unit
    tests must not wipe it. Assertions inside stay subset-based — package
    daemon threads from earlier tests may legitimately record edges
    concurrently."""
    from lance_distributed_training_tpu.utils import lockorder

    saved = lockorder.snapshot()
    lockorder.uninstall()
    lockorder.reset()
    try:
        yield lockorder
    finally:
        lockorder.restore(saved)


def test_lockorder_records_nesting_edges(lockorder_sandbox):
    lockorder = lockorder_sandbox
    a = lockorder.InstrumentedLock("x.py:1")
    b = lockorder.InstrumentedLock("x.py:2")
    with a:
        with b:
            pass
    mine = {e: n for e, n in lockorder.edges().items()
            if e[0].startswith("x.py")}
    assert mine == {("x.py:1", "x.py:2"): 1}
    with b:
        with a:
            pass
    mine = {e for e in lockorder.edges() if e[0].startswith("x.py")}
    assert mine == {("x.py:1", "x.py:2"), ("x.py:2", "x.py:1")}


def test_lockorder_rlock_reentry_records_no_self_edge(lockorder_sandbox):
    lockorder = lockorder_sandbox
    r = lockorder.InstrumentedLock("x.py:9", reentrant=True)
    with r:
        with r:
            pass
    assert all(
        src != dst for src, dst in lockorder.edges()
        if src.startswith("x.py")
    )


def test_lockorder_install_scopes_and_restores(lockorder_sandbox):
    import threading

    lockorder = lockorder_sandbox
    real_lock_type = type(threading.Lock())
    lockorder.install(scope=[str(REPO_ROOT / "tests")])
    try:
        assert lockorder.installed()
        lk = threading.Lock()  # created in tests/: instrumented
        assert isinstance(lk, lockorder.InstrumentedLock)
        assert "test_analysis.py" in lk.site
    finally:
        lockorder.uninstall()
    assert not lockorder.installed()
    assert isinstance(threading.Lock(), real_lock_type)


def test_lockorder_dump_roundtrips_through_witness_loader(
    lockorder_sandbox, tmp_path
):
    from lance_distributed_training_tpu.analysis.cli import load_lock_witness

    lockorder = lockorder_sandbox
    site_a = str(tmp_path / "pkg" / "a.py") + ":10"
    site_b = str(tmp_path / "pkg" / "b.py") + ":20"
    a = lockorder.InstrumentedLock(site_a)
    b = lockorder.InstrumentedLock(site_b)
    with a:
        with b:
            pass
    path = lockorder.dump(str(tmp_path / "witness.json"))
    witness = load_lock_witness(path, str(tmp_path))
    assert ("pkg/a.py:10", "pkg/b.py:20") in witness["edges"]
    assert witness["acquired"].get("pkg/a.py:10") == 1
    assert witness["acquired"].get("pkg/b.py:20") == 1


# -- parse cache --------------------------------------------------------------


def test_parse_cache_invalidates_on_file_change(tmp_path):
    from lance_distributed_training_tpu.analysis import CheckConfig, analyze

    config = CheckConfig(paths=["."], queue_paths=["*"])
    (tmp_path / "m.py").write_text(VIOLATION)
    assert rule_ids(analyze(str(tmp_path), config)) == ["LDT001"]
    (tmp_path / "m.py").write_text("x = 1\n")
    assert analyze(str(tmp_path), config) == []


def test_repo_program_model_sees_the_known_topology():
    """The cross-module model on the real tree: the known thread entry
    points and locks resolve, and the lease-table → registry nesting is
    the edge the coordinator docstring documents."""
    from lance_distributed_training_tpu.analysis import (
        build_program,
        load_config,
    )
    from lance_distributed_training_tpu.analysis.core import analyze_project

    root = str(REPO_ROOT)
    config = load_config(root)
    _findings, modules, _n = analyze_project(root, config)
    program = build_program(modules, config)
    targets = {t for t, _m, _n in program.spawn_sites if t is not None}
    for expected in (
        "lance_distributed_training_tpu.fleet.coordinator."
        "Coordinator._expire_loop",
        "lance_distributed_training_tpu.service.client."
        "RemoteLoader._receive",
        "lance_distributed_training_tpu.fleet.balancer._StripeRound._pump",
        "lance_distributed_training_tpu.fleet.agent.FleetAgent._run",
    ):
        assert expected in targets, sorted(targets)
    assert (
        "lance_distributed_training_tpu.fleet.coordinator.Coordinator._lock"
        in program.locks
    )
    edges = {(e.src.rsplit(".", 1)[-1], e.dst.rsplit(".", 1)[-1])
             for e in program.lock_edges}
    assert ("_lock", "_lock") in edges  # coordinator._lock -> registry._lock
    assert program.lock_cycles() == []


# -- LDT1201-1203 ownership/lifecycle (interprocedural dataflow) --------------


OWNER_FIXTURE_ROOT = REPO_ROOT / "tests" / "fixtures" / "ownermodel"

_OWNER_RESOURCES = {
    "page": {"acquire": ["Pool.lease"], "release": ["release"],
             "describe": "pool page", "idempotent": False},
    "token": {"acquire": ["Ring._acquire"], "release": ["put", "ack"],
              "describe": "slot token", "idempotent": False},
    "socket": {"acquire": ["socket.socket", "socket.create_connection"],
               "release": ["close"], "describe": "socket",
               "idempotent": True},
}

_POOL_SRC = """\
    class Pool:
        def lease(self, n):
            return bytearray(n)

        def release(self, page):
            return True
"""


def _owner_config(**kwargs):
    kwargs.setdefault("paths", ["."])
    kwargs.setdefault("queue_paths", [])
    kwargs.setdefault("resources", dict(_OWNER_RESOURCES))
    kwargs.setdefault("content_paths", [])
    kwargs.setdefault("dispatch", {})
    return CheckConfig(**kwargs)


def run_owner_rules(tmp_path, files, **config_kwargs):
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    return analyze(str(tmp_path), _owner_config(**config_kwargs))


def test_ldt1201_flags_exception_path_leak(tmp_path):
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        def decode(pool: "Pool", payloads):
            page = pool.lease(len(payloads))
            filled = transform(payloads, page)
            pool.release(page)
            return filled
    """})
    leaks = [f for f in findings if f.rule == "LDT1201"]
    assert len(leaks) == 1, [f.message for f in findings]
    assert leaks[0].path == "m.py" and leaks[0].line == 4
    assert "can raise while the handle is held" in leaks[0].message


def test_ldt1201_finally_release_is_clean(tmp_path):
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        def decode(pool: "Pool", payloads):
            page = pool.lease(len(payloads))
            try:
                return transform(payloads, page)
            finally:
                pool.release(page)
    """})
    assert [f for f in findings if f.rule.startswith("LDT12")] == []


def test_ldt1201_flags_branch_path_leak(tmp_path):
    # Released on one branch only: the other branch's exit still holds it.
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        def decode(pool: "Pool", ok):
            page = pool.lease(8)
            if ok:
                pool.release(page)
            return ok
    """})
    leaks = [f for f in findings if f.rule == "LDT1201"]
    assert len(leaks) == 1 and leaks[0].line == 4


def test_ldt1201_transfer_by_return_is_clean(tmp_path):
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        def lease_out(pool: "Pool", n):
            page = pool.lease(n)
            return page
    """})
    assert [f for f in findings if f.rule.startswith("LDT12")] == []


def test_ldt1201_transfer_through_queue_put_is_clean(tmp_path):
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        def hand_off(pool: "Pool", q, n):
            page = pool.lease(n)
            q.put(page)
    """})
    assert [f for f in findings if f.rule.startswith("LDT12")] == []


def test_ldt1201_with_managed_socket_is_clean(tmp_path):
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import socket

        def dial(host):
            with socket.create_connection((host, 80)) as sock:
                return sock.recv(1)
    """})
    assert [f for f in findings if f.rule.startswith("LDT12")] == []


def test_ldt1201_guarded_cleanup_is_clean(tmp_path):
    # The standard dial pattern: `except BaseException: if sock is not
    # None: sock.close(); raise` — the None-guard refinement must see that
    # the else branch cannot hold the socket.
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import socket

        def dial(host):
            sock = None
            try:
                sock = socket.create_connection((host, 80))
                handshake(sock)
                return sock
            except BaseException:
                if sock is not None:
                    sock.close()
                raise
    """})
    assert [f for f in findings if f.rule.startswith("LDT12")] == []


def test_ldt1201_typed_handlers_leak_other_exceptions(tmp_path):
    # `except OSError` does not catch a KeyError mid-handshake: the socket
    # escapes open — the PR 5 fd-leak class the rule exists for.
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import socket

        def dial(host):
            sock = socket.create_connection((host, 80))
            try:
                reply = handshake(sock)
                size = reply["size"]
                return sock, size
            except OSError:
                sock.close()
                raise
    """})
    leaks = [f for f in findings if f.rule == "LDT1201"]
    assert len(leaks) == 1 and leaks[0].line == 4


def test_ldt1201_generator_close_edge(tmp_path):
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        def stream(pool: "Pool", items):
            page = pool.lease(8)
            for item in items:
                fill(page, item)
                yield item
            pool.release(page)
    """})
    leaks = [f for f in findings if f.rule == "LDT1201"]
    assert len(leaks) == 1
    assert "generator close" in leaks[0].message


def test_ldt1201_generator_finally_is_clean(tmp_path):
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        def stream(pool: "Pool", items):
            page = pool.lease(8)
            try:
                for item in items:
                    fill(page, item)
                    yield item
            finally:
                pool.release(page)
    """})
    assert [f for f in findings if f.rule.startswith("LDT12")] == []


def test_ldt1201_interprocedural_acquirer_wrapper(tmp_path):
    # `_lease_out` returns a fresh lease, so its CALLERS become acquire
    # sites — the fixpoint half of the model.
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        class Decoder:
            def __init__(self, pool: "Pool"):
                self.pool = pool

            def _lease_out(self, n):
                return self.pool.lease(n)

            def decode(self, payloads):
                page = self._lease_out(len(payloads))
                transform(payloads, page)
                return None
    """})
    leaks = [f for f in findings if f.rule == "LDT1201"]
    assert len(leaks) == 1, [f.message for f in findings]
    assert leaks[0].line == 11


def test_ldt1201_interprocedural_releaser_helper(tmp_path):
    # `_give_back` releases its parameter, so calling it IS a release.
    findings = run_owner_rules(tmp_path, {"p.py": _POOL_SRC, "m.py": """\
        from p import Pool

        class Consumer:
            def __init__(self, pool: "Pool"):
                self.pool = pool

            def _give_back(self, batch):
                self.pool.release(batch)

            def consume(self, payloads):
                page = self.pool.lease(len(payloads))
                try:
                    transform(payloads, page)
                finally:
                    self._give_back(page)
    """})
    assert [f for f in findings if f.rule.startswith("LDT12")] == []


def test_ldt1201_publish_on_self_transfers(tmp_path):
    # The `_publish` handle-swap idiom: a callee storing its parameter on
    # self takes ownership.
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import socket

        class Client:
            def __init__(self):
                self._conn = None

            def _publish(self, sock):
                self._conn = sock

            def dial(self, host):
                sock = socket.create_connection((host, 80))
                self._publish(sock)

            def close(self):
                if self._conn is not None:
                    self._conn.close()
    """})
    assert [f for f in findings if f.rule.startswith("LDT12")] == []


def test_ldt1202_flags_double_release(tmp_path):
    findings = run_owner_rules(tmp_path, {"m.py": """\
        class Ring:
            def _acquire(self):
                return (0, 0, 0)

        def pump(ring, q):
            tok = ring._acquire()
            q.put(tok)
            q.put(tok)
    """})
    doubles = [f for f in findings if f.rule == "LDT1202"]
    assert len(doubles) == 1 and doubles[0].line == 8


def test_ldt1202_idempotent_kind_skips(tmp_path):
    # socket.close is declared idempotent: close-twice is legal Python.
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import socket

        def dial(host):
            sock = socket.create_connection((host, 80))
            sock.close()
            sock.close()
    """})
    assert [f for f in findings if f.rule == "LDT1202"] == []


def test_ldt1203_flags_shutdown_after_close(tmp_path):
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import socket

        def dial(host):
            sock = socket.create_connection((host, 80))
            sock.close()
            sock.shutdown(2)
    """})
    uses = [f for f in findings if f.rule == "LDT1203"]
    assert len(uses) == 1 and uses[0].line == 6


def test_ldt1203_shutdown_before_close_is_clean(tmp_path):
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import socket

        def dial(host):
            sock = socket.create_connection((host, 80))
            sock.shutdown(2)
            sock.close()
    """})
    assert [f for f in findings if f.rule == "LDT1203"] == []


def test_ldt1203_rebind_after_release_is_clean(tmp_path):
    # close-then-redial: the name now holds a FRESH handle.
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import socket

        def redial(host):
            sock = socket.create_connection((host, 80))
            sock.close()
            sock = socket.create_connection((host, 81))
            sock.shutdown(2)
            sock.close()
    """})
    assert [f for f in findings if f.rule == "LDT1203"] == []


def test_ldt12xx_ignore_requires_reason(tmp_path):
    src = """\
        from p import Pool

        def decode(pool: "Pool", payloads):
            page = pool.lease(len(payloads)){suffix}
            filled = transform(payloads, page)
            pool.release(page)
            return filled
    """
    bare = run_owner_rules(
        tmp_path, {"p.py": _POOL_SRC,
                   "m.py": src.format(suffix="  # ldt: ignore[LDT1201]")})
    assert [f.rule for f in bare if f.rule == "LDT1201"] == ["LDT1201"]
    (tmp_path / "m.py").write_text(textwrap.dedent(src.format(
        suffix="  # ldt: ignore[LDT1201] -- bench-only path, GC reclaims"
    )))
    reasoned = analyze(str(tmp_path), _owner_config())
    assert [f for f in reasoned if f.rule == "LDT1201"] == []


# -- LDT1301 content-purity taint ---------------------------------------------


def test_ldt1301_flags_wall_clock_in_content_path(tmp_path):
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import time

        def build_plan(n):
            jitter = time.time()
            return [(i, jitter) for i in range(n)]
    """}, content_paths=["m.py"])
    taints = [f for f in findings if f.rule == "LDT1301"]
    assert len(taints) == 1 and taints[0].line == 4
    assert "time.time" in taints[0].message


def test_ldt1301_flags_taint_via_reachable_callee(tmp_path):
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import random

        def build_plan(n):
            return _order(n)

        def _order(n):
            return sorted(range(n), key=lambda _i: random.random())
    """}, content_paths=["m.py::*.build_plan"])
    taints = [f for f in findings if f.rule == "LDT1301"]
    assert len(taints) == 1 and taints[0].line == 7
    assert "reachable from content path" in taints[0].message


def test_ldt1301_out_of_scope_module_is_silent(tmp_path):
    findings = run_owner_rules(tmp_path, {"telemetry.py": """\
        import time

        def stamp():
            return time.time()
    """}, content_paths=["content/*.py"])
    assert [f for f in findings if f.rule == "LDT1301"] == []


def test_ldt1301_queue_pop_and_set_iteration_sources(tmp_path):
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import queue

        class Assembler:
            def __init__(self, depth):
                self.q = queue.Queue(maxsize=depth)

            def next_batch(self):
                return self.q.get_nowait()

        def merge(names):
            out = []
            for n in set(names):
                out.append(n)
            return out
    """}, content_paths=["m.py"])
    taints = sorted(f.line for f in findings if f.rule == "LDT1301")
    assert taints == [8, 12], [f.message for f in findings]


def test_ldt1301_seeded_rng_is_clean(tmp_path):
    findings = run_owner_rules(tmp_path, {"m.py": """\
        import numpy as np

        def build_plan(n, seed):
            return np.random.default_rng(seed).permutation(n)
    """}, content_paths=["m.py"])
    assert [f for f in findings if f.rule == "LDT1301"] == []


# -- the seeded ownermodel fixture package ------------------------------------


def _ownermodel_fixture_config(**kwargs):
    kwargs.setdefault("paths", ["pkg"])
    kwargs.setdefault("content_paths", ["pkg/content.py"])
    kwargs.setdefault("protocol_module", "pkg/absent.py")
    return _owner_config(**kwargs)


def test_ownermodel_fixture_yields_exactly_the_planted_findings():
    findings = analyze(str(OWNER_FIXTURE_ROOT), _ownermodel_fixture_config())
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("LDT1301", "pkg/content.py", 12),
        ("LDT1301", "pkg/content.py", 21),
        ("LDT1201", "pkg/leaky.py", 9),
        ("LDT1201", "pkg/leaky.py", 16),
        ("LDT1202", "pkg/leaky.py", 26),
        ("LDT1203", "pkg/leaky.py", 32),
    ], [f"{f.rule} {f.location()}" for f in findings]


def test_leak_witness_reproduces_observed_leak():
    config = _ownermodel_fixture_config()
    config.leak_witness = {"sites": {
        "pkg/leaky.py:9": {"acquired": 6, "released": 4, "leaked": 2},
    }}
    findings = analyze(str(OWNER_FIXTURE_ROOT), config)
    leak = next(f for f in findings
                if f.rule == "LDT1201" and f.line == 9)
    assert leak.witness_pruned is False
    assert "reproduced leak" in leak.message


def test_leak_witness_prunes_balanced_site():
    config = _ownermodel_fixture_config()
    config.leak_witness = {"sites": {
        "pkg/leaky.py:9": {"acquired": 6, "released": 6, "leaked": 0},
    }}
    findings = analyze(str(OWNER_FIXTURE_ROOT), config)
    leak = next(f for f in findings
                if f.rule == "LDT1201" and f.line == 9)
    assert leak.witness_pruned is True
    assert "witness_pruned" in leak.message
    # The other planted leak has no evidence either way: stays live.
    other = next(f for f in findings
                 if f.rule == "LDT1201" and f.line == 16)
    assert other.witness_pruned is False


def test_leak_witness_without_exercise_does_not_prune():
    config = _ownermodel_fixture_config()
    config.leak_witness = {"sites": {
        "pkg/leaky.py:9": {"acquired": 0, "released": 0, "leaked": 0},
    }}
    findings = analyze(str(OWNER_FIXTURE_ROOT), config)
    leak = next(f for f in findings
                if f.rule == "LDT1201" and f.line == 9)
    assert leak.witness_pruned is False


def test_check_main_leak_witness_end_to_end(tmp_path):
    pytest.importorskip("tomli")
    site = str(OWNER_FIXTURE_ROOT / "pkg" / "leaky.py") + ":9"
    witness = {
        "version": 1,
        "sites": {site: {"acquired": 5, "released": 5, "leaked": 0}},
        "leaked": [],
    }
    wpath = tmp_path / "leak-witness.json"
    wpath.write_text(json.dumps(witness))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(OWNER_FIXTURE_ROOT), "--json", "--no-baseline",
         "--leak-witness", str(wpath)],
        out=out,
    )
    assert rc == 1  # the other seeds still fail the gate
    data = json.loads(out.getvalue())
    pruned = next(f for f in data["findings"]
                  if f["rule"] == "LDT1201" and f["line"] == 9)
    assert pruned["witness_pruned"] is True
    assert pruned["rule_family"] == "ownership"
    live = next(f for f in data["findings"]
                if f["rule"] == "LDT1201" and f["line"] == 16)
    assert live["witness_pruned"] is False
    # The corroboration receipt: 1 runtime site, 1 matched, 0 leaked.
    assert data["leak_witness"] == {
        "runtime_sites": 1, "matched_sites": 1, "leaked_sites": 0,
    }


def test_check_main_leak_witness_text_summary(tmp_path):
    pytest.importorskip("tomli")
    site = str(OWNER_FIXTURE_ROOT / "pkg" / "leaky.py") + ":9"
    wpath = tmp_path / "leak-witness.json"
    wpath.write_text(json.dumps({
        "version": 1,
        "sites": {site: {"acquired": 2, "released": 1, "leaked": 1}},
        "leaked": [],
    }))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(OWNER_FIXTURE_ROOT), "--no-baseline",
         "--leak-witness", str(wpath)],
        out=out,
    )
    assert rc == 1
    text = out.getvalue()
    assert "leak witness: 1/1 runtime sites match static acquire sites, " \
           "1 leaked" in text
    repro = [ln for ln in text.splitlines()
             if "LDT1201" in ln and "leaky.py:9" in ln]
    assert repro and "reproduced leak" in repro[0]


# -- runtime leak sanitizer (utils/leaktrack.py) ------------------------------


@pytest.fixture()
def leaktrack_sandbox():
    """Snapshot/restore the recorder around tests that enable or reset it
    (a sanitizer-enabled tier-1 session collects its witness ACROSS the
    suite — same discipline as lockorder_sandbox)."""
    from lance_distributed_training_tpu.utils import leaktrack

    saved = leaktrack.snapshot()
    leaktrack.disable()
    leaktrack.reset()
    try:
        yield leaktrack
    finally:
        leaktrack.restore(saved)


def test_leaktrack_records_buffer_pool_lease_release(leaktrack_sandbox):
    from lance_distributed_training_tpu.data.buffers import BufferPool
    from lance_distributed_training_tpu.obs.registry import MetricsRegistry

    leaktrack = leaktrack_sandbox
    leaktrack.enable()
    pool = BufferPool(registry=MetricsRegistry())
    page = pool.lease((4, 4), "uint8")
    lease_line = None
    for site, entry in leaktrack.sites().items():
        if site.endswith("test_analysis.py:" + str(_lease_call_line())):
            lease_line = entry
    assert lease_line is not None, leaktrack.sites()
    assert lease_line["acquired"] == 1
    assert lease_line["leaked"] == 1  # not yet released: would leak now
    assert pool.release(page) is True
    (entry,) = [e for s, e in leaktrack.sites().items()
                if "test_analysis.py" in s]
    assert entry == {"acquired": 1, "released": 1, "leaked": 0}


def _lease_call_line() -> int:
    """Line number of the `pool.lease((4, 4), ...)` call above — the site
    the runtime recorder must attribute the lease to."""
    import inspect

    src, start = inspect.getsourcelines(
        test_leaktrack_records_buffer_pool_lease_release
    )
    for i, line in enumerate(src):
        if "pool.lease((4, 4)" in line:
            return start + i
    raise AssertionError("lease call not found")


def test_leaktrack_dropped_lease_counts_as_leak(leaktrack_sandbox):
    from lance_distributed_training_tpu.data.buffers import BufferPool
    from lance_distributed_training_tpu.obs.registry import MetricsRegistry

    leaktrack = leaktrack_sandbox
    leaktrack.enable()
    pool = BufferPool(registry=MetricsRegistry())
    page = pool.lease((2, 2), "uint8")
    del page  # dropped without release: the weakref callback fires
    import gc

    gc.collect()
    (entry,) = [e for s, e in leaktrack.sites().items()
                if "test_analysis.py" in s]
    assert entry["leaked"] == 1 and entry["released"] == 0


def test_leaktrack_dump_roundtrips_through_witness_loader(
    leaktrack_sandbox, tmp_path
):
    from lance_distributed_training_tpu.analysis.cli import load_leak_witness

    leaktrack = leaktrack_sandbox
    leaktrack.enable()

    def fake_lease():
        leaktrack.track_acquire("pool-page", 1234, depth=2)

    fake_lease()
    leaktrack.track_release("pool-page", 1234)
    fake_lease()  # second acquisition never released: leaked at dump
    path = leaktrack.dump(str(tmp_path / "witness.json"))
    witness = load_leak_witness(path, str(REPO_ROOT / "tests"))
    (site, entry), = witness["sites"].items()
    assert site.startswith("test_analysis.py:")
    assert entry == {"acquired": 2, "released": 1, "leaked": 1}


# -- shared-model / timing receipts -------------------------------------------


def test_owner_model_is_shared_per_run(monkeypatch):
    """The satellite contract: one ProgramInfo parse pass, one OwnerModel
    build, shared by every LDT12xx/LDT13xx rule in a run."""
    import lance_distributed_training_tpu.analysis.ownermodel as om

    calls = {"n": 0}
    real_init = om.OwnerModel.__init__

    def counting_init(self, program, config):
        calls["n"] += 1
        real_init(self, program, config)

    monkeypatch.setattr(om.OwnerModel, "__init__", counting_init)
    analyze(str(OWNER_FIXTURE_ROOT), _ownermodel_fixture_config())
    assert calls["n"] == 1


def test_json_reports_model_build_ms(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    out = io.StringIO()
    rc = check_main(["--root", str(tmp_path), ".", "--json"], out=out)
    assert rc == 0
    data = json.loads(out.getvalue())
    build = data["model_build_ms"]
    assert set(build) == {"concurrency", "protocol", "ownership", "mesh"}
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in build.values())


def test_repo_ldt_check_stays_under_wall_budget():
    """The parse-once/one-model-per-family contract, asserted as a budget
    on the full repo self-check: the whole `ldt check` pass (parse + the
    cross-module models + every rule family) must stay an every-commit
    gate, not a coffee break. The check is one thread of Python, so the
    budget is on this process's own CPU time, which the load of the other
    test workers on the machine does not move as it moves the wall clock:
    about three times what the pass takes alone (11 s of CPU: parse 0.7,
    protocol model 3.3, ownership 2.4, mesh 1.6) — a quadratic regression
    blows through it anyway. Every family's model is built and timed."""
    out = io.StringIO()
    cpu0 = time.process_time()
    rc = check_main(["--root", str(REPO_ROOT), "--json"], out=out)
    cpu_s = time.process_time() - cpu0
    assert rc == 0, out.getvalue()
    data = json.loads(out.getvalue())
    assert cpu_s < 33.0, (cpu_s, data["wall_time_ms"], data["model_build_ms"])
    assert all(ms > 0 for ms in data["model_build_ms"].values())


# -- ldt graph --ownership ----------------------------------------------------


def test_graph_ownership_dot_smoke():
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(
        ["--root", str(OWNER_FIXTURE_ROOT), "pkg", "--dot", "--ownership"],
        out=out,
    )
    assert rc == 0
    dot = out.getvalue()
    assert '"res:page"' in dot and "shape=diamond" in dot
    # The planted leak renders as a RED edge; a clean acquire stays green.
    assert 'LEAK pkg/leaky.py:9' in dot
    assert '#dc2626' in dot and '#16a34a' in dot


def test_graph_ownership_text_smoke():
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(
        ["--root", str(OWNER_FIXTURE_ROOT), "pkg", "--ownership"], out=out
    )
    assert rc == 0
    text = out.getvalue()
    assert "ownership model:" in text
    assert "LEAK(exception)" in text
    assert "resource token acquired in leaky.double_put" in text


def test_graph_ownership_cli_dispatch():
    import lance_distributed_training_tpu.cli as cli

    rc = cli.main(["graph", "--root", str(OWNER_FIXTURE_ROOT), "pkg",
                   "--ownership"])
    assert rc == 0


# -- LDT1401-1404 wire-protocol evolution (analysis/protomodel.py) ------------


PROTO_FIXTURE_ROOT = REPO_ROOT / "tests" / "fixtures" / "protomodel"


def _proto_config(**kwargs):
    kwargs.setdefault("paths", ["pkg"])
    kwargs.setdefault("queue_paths", [])
    kwargs.setdefault("protocol_module", "pkg/proto.py")
    kwargs.setdefault("protocol_binary", [])
    kwargs.setdefault(
        "protocol_versions", {"MSG_PING.feature": "FEATURE_MIN_VERSION"}
    )
    kwargs.setdefault("dispatch", {})
    kwargs.setdefault("content_paths", [])
    return CheckConfig(**kwargs)


_WIRE_PROTO = """\
    MSG_A = 1
    MSG_B = 2
    PROTOCOL_VERSION = 3
    GADGET_MIN_VERSION = 3

    def send_msg(sock, msg_type, payload):
        sock.sendall(payload)

    def recv_msg(sock):
        return MSG_A, {}
"""


def _wire_rules(tmp_path, files, **kwargs):
    files = dict(files)
    files.setdefault("proto.py", _WIRE_PROTO)
    kwargs.setdefault("protocol_module", "proto.py")
    kwargs.setdefault("protocol_binary", [])
    kwargs.setdefault("protocol_versions", {})
    kwargs.setdefault("dispatch", {})
    kwargs.setdefault("content_paths", [])
    return run_rules(tmp_path, files, **kwargs)


def test_ldt1401_flags_written_never_read_field(tmp_path):
    findings = _wire_rules(tmp_path, {
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A,
                               {"used": 1, "forgotten": 2})
        """,
        "reader.py": """\
            import proto

            def handle(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return req.get("used")
        """,
    })
    assert rule_ids(findings) == ["LDT1401"]
    assert findings[0].path == "writer.py"
    assert "'forgotten'" in findings[0].message


def test_ldt1401_protocol_module_reads_do_not_count(tmp_path):
    """The schema owner validating its own dict proves nothing about the
    peer — exactly why deleting a decode_config_skew check must fail."""
    findings = _wire_rules(tmp_path, {
        "proto.py": _WIRE_PROTO + """\

    def validate(req):
        return req.get("knob") is not None
    """,
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"knob": 1})
        """,
    })
    assert rule_ids(findings) == ["LDT1401"]
    assert "'knob'" in findings[0].message


def test_ldt1401_interprocedural_skew_check_read_satisfies(tmp_path):
    """A read through a parameter-passed helper (the decode_config_skew
    shape: run() hands the HELLO dict to a checker) counts."""
    findings = _wire_rules(tmp_path, {
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"knob": 1})
        """,
        "reader.py": """\
            import proto

            def skew(req):
                return req.get("knob")

            def handle(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return skew(req)
        """,
    })
    assert findings == []


def test_ldt1401_constructor_function_writes_tracked(tmp_path):
    """Fields written through a dict-returning constructor (the
    protocol.hello shape) are write sites at the constructor's key
    lines."""
    findings = _wire_rules(tmp_path, {
        "proto.py": _WIRE_PROTO + """\

    def make_a(knob):
        return {"knob": knob, "dead": 0}
    """,
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, proto.make_a(3))
        """,
        "reader.py": """\
            import proto

            def handle(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return req.get("knob")
        """,
    })
    assert rule_ids(findings) == ["LDT1401"]
    assert findings[0].path == "proto.py" and "'dead'" in findings[0].message


def test_ldt1402_flags_ungated_versioned_read(tmp_path):
    findings = _wire_rules(tmp_path, {
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"gadget": 1})
        """,
        "reader.py": """\
            import proto

            def handle(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return req.get("gadget")
        """,
    }, protocol_versions={"MSG_A.gadget": "GADGET_MIN_VERSION"})
    assert rule_ids(findings) == ["LDT1402"]
    assert "GADGET_MIN_VERSION" in findings[0].message


def test_ldt1402_gate_in_function_passes(tmp_path):
    findings = _wire_rules(tmp_path, {
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"gadget": 1})
        """,
        "reader.py": """\
            import proto

            def handle(sock, peer_version):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                if peer_version < proto.GADGET_MIN_VERSION:
                    raise ValueError(peer_version)
                return req.get("gadget")
        """,
    }, protocol_versions={"MSG_A.gadget": "GADGET_MIN_VERSION"})
    assert findings == []


def test_ldt1402_gate_in_caller_passes(tmp_path):
    """The balancer._hello shape: the helper serving the gated field has
    no guard of its own, but its only caller does."""
    findings = _wire_rules(tmp_path, {
        "writer.py": """\
            import proto

            def build(gadget):
                return {"gadget": gadget}

            def helper(sock, gadget):
                proto.send_msg(sock, proto.MSG_A, build(gadget=gadget))

            def send(sock, peer_version):
                if peer_version < proto.GADGET_MIN_VERSION:
                    raise ValueError(peer_version)
                helper(sock, 1)
        """,
        "reader.py": """\
            import proto

            def handle(sock, peer_version):
                msg_type, req = proto.recv_msg(sock)
                if peer_version < proto.GADGET_MIN_VERSION:
                    raise ValueError(peer_version)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return req.get("gadget")
        """,
    }, protocol_versions={"MSG_A.gadget": "GADGET_MIN_VERSION"})
    assert findings == []


def test_ldt1402_kwarg_serve_fires_for_qualified_gate_keys(tmp_path):
    """Regression: the keyword-serve half (passing a gated field into a
    schema constructor) must fire for 'MSG_X.field'-qualified config
    entries — the shipped pyproject uses only those; a bare-name
    pre-filter silently disabled the serve check."""
    files = {
        "proto.py": _WIRE_PROTO + """\

    def make_a(gadget):
        return {"gadget": gadget}
    """,
        "writer.py": """\
            import proto

            def send(sock, gadget):
                proto.send_msg(sock, proto.MSG_A, proto.make_a(
                    gadget=gadget
                ))
        """,
        "reader.py": """\
            import proto

            def handle(sock, peer_version):
                msg_type, req = proto.recv_msg(sock)
                if peer_version < proto.GADGET_MIN_VERSION:
                    raise ValueError(peer_version)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return req.get("gadget")
        """,
    }
    ungated = _wire_rules(
        tmp_path, files,
        protocol_versions={"MSG_A.gadget": "GADGET_MIN_VERSION"},
    )
    assert rule_ids(ungated) == ["LDT1402"]
    assert ungated[0].path == "writer.py"
    # The same serve under a guard is the negative control.
    guarded = dict(files)
    guarded["writer.py"] = """\
        import proto

        def send(sock, gadget, peer_version):
            if peer_version < proto.GADGET_MIN_VERSION:
                raise ValueError(peer_version)
            proto.send_msg(sock, proto.MSG_A, proto.make_a(
                gadget=gadget
            ))
    """
    assert _wire_rules(
        tmp_path, guarded,
        protocol_versions={"MSG_A.gadget": "GADGET_MIN_VERSION"},
    ) == []


def test_ldt1402_recursive_helpers_under_a_guarded_entry_pass(tmp_path):
    """Regression: a gated read inside a mutually recursive helper chain
    whose only external entry holds the guard is guarded — the recursion
    back-edge is not an unguarded entry path (the SCC fixpoint, not a
    path-order-dependent DFS)."""
    findings = _wire_rules(tmp_path, {
        "reader.py": """\
            import proto

            def use(req):
                return req.get("gadget")

            def rec(req, n):
                if n:
                    return rec2(req, n - 1)
                return use(req)

            def rec2(req, n):
                return rec(req, n)

            def entry(sock, peer_version):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                if peer_version < proto.GADGET_MIN_VERSION:
                    raise ValueError(peer_version)
                return rec(req, 3)
        """,
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"gadget": 1})
        """,
    }, protocol_versions={"MSG_A.gadget": "GADGET_MIN_VERSION"})
    assert findings == []


def test_ldt1402_recursion_under_unguarded_entry_stays_flagged(tmp_path):
    """The sound direction: the SCC fixpoint must not launder a cycle
    into guardedness when its external entry has no guard."""
    findings = _wire_rules(tmp_path, {
        "reader.py": """\
            import proto

            def handle(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return loop_a(req, 2)

            def loop_a(req, n):
                if n:
                    return loop_b(req, n - 1)
                return req.get("gadget")

            def loop_b(req, n):
                return loop_a(req, n)
        """,
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"gadget": 1})
        """,
    }, protocol_versions={"MSG_A.gadget": "GADGET_MIN_VERSION"})
    assert rule_ids(findings) == ["LDT1402"]


def test_ldt1402_config_drift_is_a_finding(tmp_path):
    findings = _wire_rules(tmp_path, {
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"x": 1})
        """,
        "reader.py": """\
            import proto

            def handle(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return req.get("x")
        """,
    }, protocol_versions={"MSG_A.x": "ABSENT_MIN_VERSION"})
    drift = [f for f in findings if f.rule == "LDT1402"]
    assert drift and "ABSENT_MIN_VERSION" in drift[0].message
    assert "config drift" in drift[0].message


def test_ldt1403_flags_read_without_writer(tmp_path):
    findings = _wire_rules(tmp_path, {
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"real": 1})
        """,
        "reader.py": """\
            import proto

            def handle(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                return req.get("real"), req.get("phantom")
        """,
    })
    assert rule_ids(findings) == ["LDT1403"]
    assert findings[0].path == "reader.py"
    assert "'phantom'" in findings[0].message


def test_ldt1403_handler_dict_reads_attributed(tmp_path):
    """The coordinator shape: handlers dispatched through a
    {MSG: method} dict get their request parameter's message role."""
    findings = _wire_rules(tmp_path, {
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"real": 1})
        """,
        "reader.py": """\
            import proto

            class Handler:
                def _on_a(self, req):
                    return req.get("real"), req.get("specter")

                def serve(self, sock):
                    msg_type, req = proto.recv_msg(sock)
                    handler = {proto.MSG_A: self._on_a}.get(msg_type)
                    if handler is None:
                        raise ValueError(msg_type)
                    return handler(req)
        """,
    })
    assert rule_ids(findings) == ["LDT1403"]
    assert "'specter'" in findings[0].message


def test_ldt1404_flags_struct_outside_protocol_module(tmp_path):
    findings = _wire_rules(tmp_path, {
        "framer.py": """\
            import struct

            def frame(payload):
                return struct.pack(">I", len(payload)) + payload
        """,
    })
    assert rule_ids(findings) == ["LDT1404"]
    assert "struct.pack" in findings[0].message


def test_ldt1404_protocol_module_framing_allowed(tmp_path):
    findings = _wire_rules(tmp_path, {
        "proto.py": """\
            import struct

            MSG_A = 1
            _HEADER = struct.Struct(">IB")

            def send_msg(sock, msg_type, payload):
                sock.sendall(struct.pack(">I", len(payload)))

            def recv_msg(sock):
                return MSG_A, {}
        """,
    })
    assert findings == []


def test_ldt14xx_ignores_require_reason(tmp_path):
    bare = _wire_rules(tmp_path, {
        "framer.py": """\
            import struct

            def frame(payload):
                return struct.pack(">I", 0) + payload  # ldt: ignore[LDT1404]
        """,
    })
    assert rule_ids(bare) == ["LDT1404"]  # reasonless: stays live
    reasoned = _wire_rules(tmp_path, {
        "framer.py": """\
            import struct

            def frame(payload):
                return struct.pack(">I", 0) + payload  # ldt: ignore[LDT1404] -- bench-only fake frame, never on a real wire
        """,
    })
    assert reasoned == []


# -- the seeded protomodel fixture package ------------------------------------


def test_protomodel_fixture_yields_exactly_the_planted_findings():
    findings = analyze(str(PROTO_FIXTURE_ROOT), _proto_config())
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("LDT1404", "pkg/framing.py", 7),
        ("LDT1401", "pkg/proto.py", 28),
        ("LDT1402", "pkg/server.py", 13),
        ("LDT1403", "pkg/server.py", 14),
    ], [f"{f.rule} {f.location()}" for f in findings]


def test_wire_witness_prunes_observed_orphan_read():
    """A (msg, field) tuple the instrumented run saw on the wire proves a
    writer outside the static view — the LDT1403 finding renders pruned."""
    config = _proto_config()
    config.wire_witness = {
        "frames": {"1": 6}, "fields": {"1": {"ghost": 4}},
    }
    findings = analyze(str(PROTO_FIXTURE_ROOT), config)
    orphan = next(f for f in findings if f.rule == "LDT1403")
    assert orphan.witness_pruned is True
    assert "witness_pruned" in orphan.message


def test_wire_witness_reproduces_dead_read():
    """Message exercised, field never crossed: the orphan read upgrades
    from inference to reproduced — and still fails the gate."""
    config = _proto_config()
    config.wire_witness = {"frames": {"1": 6}, "fields": {"1": {}}}
    findings = analyze(str(PROTO_FIXTURE_ROOT), config)
    orphan = next(f for f in findings if f.rule == "LDT1403")
    assert orphan.witness_pruned is False
    assert "reproduced dead read" in orphan.message


def test_wire_witness_without_exercise_changes_nothing():
    config = _proto_config()
    config.wire_witness = {"frames": {"2": 9}, "fields": {}}
    findings = analyze(str(PROTO_FIXTURE_ROOT), config)
    orphan = next(f for f in findings if f.rule == "LDT1403")
    assert orphan.witness_pruned is False
    assert "witness" not in orphan.message


def test_check_main_wire_witness_end_to_end(tmp_path):
    pytest.importorskip("tomli")
    wpath = tmp_path / "wire-witness.json"
    wpath.write_text(json.dumps({
        "version": 1,
        "frames": {"1": 6},
        "fields": {"1": {"ghost": 4, "payload_size": 6}},
    }))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(PROTO_FIXTURE_ROOT), "--json", "--no-baseline",
         "--wire-witness", str(wpath)],
        out=out,
    )
    assert rc == 1  # the other seeds still fail the gate
    data = json.loads(out.getvalue())
    pruned = next(f for f in data["findings"] if f["rule"] == "LDT1403")
    assert pruned["witness_pruned"] is True
    assert pruned["rule_family"] == "wire-protocol"
    # The corroboration receipt: both observed fields map onto the static
    # schema (ghost is a known read, payload_size a known write+read).
    assert data["wire_witness"] == {
        "observed_fields": 2, "matched_fields": 2, "frames": 6,
        "versions_seen": [],
    }
    assert "protocol" in data["model_build_ms"]


def test_check_main_wire_witness_text_summary(tmp_path):
    pytest.importorskip("tomli")
    wpath = tmp_path / "wire-witness.json"
    wpath.write_text(json.dumps({
        "version": 1, "frames": {"1": 3},
        "fields": {"1": {"payload_size": 3}},
    }))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(PROTO_FIXTURE_ROOT), "--no-baseline",
         "--wire-witness", str(wpath)],
        out=out,
    )
    assert rc == 1
    assert ("wire witness: 1/1 observed (msg, field) tuples match the "
            "static schema over 3 frames") in out.getvalue()


def test_check_main_unreadable_wire_witness_is_usage_error(tmp_path):
    bad = tmp_path / "nope.json"
    bad.write_text("{torn")
    out = io.StringIO()
    rc = check_main(
        ["--root", str(PROTO_FIXTURE_ROOT), "--no-baseline",
         "--wire-witness", str(bad)],
        out=out,
    )
    assert rc == 2
    assert "unreadable wire witness" in out.getvalue()


def test_check_main_non_numeric_witness_key_is_usage_error(tmp_path):
    """Message keys are numeric on the wire; a hand-edited witness with a
    symbolic key must die at LOAD time (exit 2, diagnosable) — never as a
    mid-analysis int() traceback inside the receipt."""
    bad = tmp_path / "symbolic.json"
    bad.write_text(json.dumps({
        "version": 1, "frames": {"MSG_HELLO": 3},
        "fields": {"MSG_HELLO": {"seed": 1}},
    }))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(PROTO_FIXTURE_ROOT), "--no-baseline",
         "--wire-witness", str(bad)],
        out=out,
    )
    assert rc == 2
    assert "unreadable wire witness" in out.getvalue()


def test_wire_witness_versions_ride_the_receipt(tmp_path):
    pytest.importorskip("tomli")
    wpath = tmp_path / "wire-witness.json"
    wpath.write_text(json.dumps({
        "version": 1, "frames": {"1": 4},
        "fields": {"1": {"payload_size": 4}},
        "versions": {"1": [1, 3]},
    }))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(PROTO_FIXTURE_ROOT), "--json", "--no-baseline",
         "--wire-witness", str(wpath)],
        out=out,
    )
    assert rc == 1
    data = json.loads(out.getvalue())
    assert data["wire_witness"]["versions_seen"] == [1, 3]
    out = io.StringIO()
    check_main(
        ["--root", str(PROTO_FIXTURE_ROOT), "--no-baseline",
         "--wire-witness", str(wpath)],
        out=out,
    )
    assert "(versions seen: 1, 3)" in out.getvalue()


def test_ldt1402_diamond_caller_graph_is_guarded(tmp_path):
    """Regression: two guarded caller paths sharing an unguarded
    intermediate must not be mistaken for an unguarded cycle — the memo
    distinguishes a completed verdict from an on-path revisit."""
    findings = _wire_rules(tmp_path, {
        "reader.py": """\
            import proto

            def use(req):
                return req.get("gadget")

            def middle(req):
                return use(req)

            def path_a(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                if 3 < proto.GADGET_MIN_VERSION:
                    raise ValueError()
                return middle(req)

            def path_b(sock):
                msg_type, req = proto.recv_msg(sock)
                if msg_type != proto.MSG_A:
                    raise ValueError(msg_type)
                if 3 < proto.GADGET_MIN_VERSION:
                    raise ValueError()
                return middle(req)
        """,
        "writer.py": """\
            import proto

            def send(sock):
                proto.send_msg(sock, proto.MSG_A, {"gadget": 1})
        """,
    }, protocol_versions={"MSG_A.gadget": "GADGET_MIN_VERSION"})
    assert findings == []


def test_proto_model_is_shared_per_run(monkeypatch):
    """One ProgramInfo parse pass, one ProtoModel build, shared by the
    three LDT14xx whole-program rules in a run."""
    import lance_distributed_training_tpu.analysis.protomodel as pm

    calls = {"n": 0}
    real_init = pm.ProtoModel.__init__

    def counting_init(self, program, config):
        calls["n"] += 1
        real_init(self, program, config)

    monkeypatch.setattr(pm.ProtoModel, "__init__", counting_init)
    analyze(str(PROTO_FIXTURE_ROOT), _proto_config())
    assert calls["n"] == 1


def test_repo_protocol_schema_is_fully_paired():
    """The repo self-check at field level: every payload field some peer
    writes is read (or skew-checked) by the other side, and vice versa —
    the machine-checked form of the hand-maintained HELLO contract."""
    from lance_distributed_training_tpu.analysis.config import load_config
    from lance_distributed_training_tpu.analysis.core import parse_modules
    from lance_distributed_training_tpu.analysis.concmodel import (
        build_program,
    )
    from lance_distributed_training_tpu.analysis.protomodel import (
        build_proto_model,
    )

    config = load_config(str(REPO_ROOT))
    modules, _, _ = parse_modules(str(REPO_ROOT), config)
    model = build_proto_model(build_program(modules, config), config)
    # Every HELLO field the model knows is covered by a server-side read:
    # the decode_config_skew contract, now structural.
    hello = model.messages["MSG_HELLO"]
    assert set(hello.writes) == set(hello.reads)
    for field in ("task_type", "image_size", "device_decode",
                  "dataset_fingerprint", "stripe_index", "stripe_count"):
        assert field in hello.reads, f"HELLO {field} lost its peer read"
    assert model.orphan_writes() == []
    assert model.orphan_reads() == []
    assert model.ungated_sites == []


# -- ldt graph --protocol -----------------------------------------------------


def test_graph_protocol_text_smoke():
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(["--root", str(REPO_ROOT), "--protocol"], out=out)
    assert rc == 0
    text = out.getvalue()
    assert "protocol model:" in text
    assert "msg MSG_HELLO:" in text
    assert ">=STRIPE_MIN_VERSION" in text
    assert "msg MSG_BATCH: binary payload" in text


def test_graph_protocol_dot_smoke():
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(
        ["--root", str(PROTO_FIXTURE_ROOT), "pkg", "--dot", "--protocol"],
        out=out,
    )
    assert rc == 0
    dot = out.getvalue()
    assert '"msg:MSG_PING"' in dot and "shape=hexagon" in dot


def test_graph_protocol_cli_dispatch():
    import lance_distributed_training_tpu.cli as cli

    rc = cli.main(["graph", "--root", str(PROTO_FIXTURE_ROOT), "pkg",
                   "--protocol"])
    assert rc == 0


def test_deleting_a_skew_check_fails_ldt1401_at_the_field():
    """THE acceptance criterion: neuter one decode_config_skew read (the
    device_decode check) in an in-memory copy of server.py and the model
    must report the field as written-but-unchecked — at protocol.hello's
    field line, with the real repo as every other module."""
    from lance_distributed_training_tpu.analysis.config import load_config
    from lance_distributed_training_tpu.analysis.core import (
        ModuleInfo,
        parse_modules,
    )
    from lance_distributed_training_tpu.analysis.concmodel import (
        build_program,
    )
    from lance_distributed_training_tpu.analysis.protomodel import (
        build_proto_model,
    )

    config = load_config(str(REPO_ROOT))
    modules, _, _ = parse_modules(str(REPO_ROOT), config)
    server = next(
        m for m in modules if m.relpath.endswith("service/server.py")
    )
    mutated_src = server.source.replace(
        'dd = req.get("device_decode")', "dd = None"
    )
    assert mutated_src != server.source  # the check exists to be deleted
    mutated = ModuleInfo(server.root, server.relpath, mutated_src)
    modules = [mutated if m is server else m for m in modules]
    model = build_proto_model(build_program(modules, config), config)
    orphans = {(s.msg, s.field) for s in model.orphan_writes()}
    assert ("MSG_HELLO", "device_decode") in orphans
    site = next(
        s for s in model.orphan_writes() if s.field == "device_decode"
    )
    # Reported at the field's write site in the schema owner — the
    # protocol module's hello() constructor.
    assert site.module.endswith("service/protocol.py")


# -- LDT1501 padding hygiene --------------------------------------------------


def test_ldt1501_flags_np_pad_on_hot_path(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import numpy as np

        def collate(values, width):
            return np.pad(values, (0, width - len(values)))
    """}, hot_paths=["*"])
    hits = [f for f in findings if f.rule == "LDT1501"]
    assert len(hits) == 1
    assert "token_pack" in hits[0].message


def test_ldt1501_flags_full_max_len_allocation(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import numpy as np

        def collate(rows, seq_len, pad_id):
            page = np.full((len(rows), seq_len), pad_id)
            grid = np.zeros((4, 8))  # content-sized: fine
            return page, grid
    """}, hot_paths=["*"])
    hits = [f for f in findings if f.rule == "LDT1501"]
    assert len(hits) == 1
    assert "max-length token grid" in hits[0].message


def test_ldt1501_flags_attribute_shaped_max_allocation(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import numpy as np

        class Decoder:
            def collate(self, rows):
                return np.empty((len(rows), self.max_len), np.int32)
    """}, hot_paths=["*"])
    assert [f.rule for f in findings if f.rule == "LDT1501"] == ["LDT1501"]


def test_ldt1501_exempts_token_pack_module(tmp_path):
    findings = run_rules(tmp_path, {"token_pack.py": """\
        import numpy as np

        def pad(values, seq_len, pad_id):
            page = np.full((len(values), seq_len), pad_id)
            return np.pad(page, 1)
    """}, hot_paths=["*"])
    assert [f for f in findings if f.rule == "LDT1501"] == []


def test_ldt1501_silent_off_hot_paths(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import numpy as np

        def debug_tool(values, max_len):
            return np.zeros((len(values), max_len))
    """}, hot_paths=["somewhere/else.py"])
    assert [f for f in findings if f.rule == "LDT1501"] == []


def test_ldt1501_content_sized_allocations_pass(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        import numpy as np

        def collate(lengths, values):
            width = int(lengths.max())
            page = np.zeros((len(lengths), width), values.dtype)
            return page
    """}, hot_paths=["*"])
    assert [f for f in findings if f.rule == "LDT1501"] == []


# -- LDT1601 graph hygiene ----------------------------------------------------


def test_ldt1601_flags_engine_construction_on_hot_path(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        from lance_distributed_training_tpu.data.pipeline import DataPipeline

        def build(ds, plan, decode):
            return DataPipeline(ds, plan, decode, None, 2)
    """}, hot_paths=["*"])
    hits = [f for f in findings if f.rule == "LDT1601"]
    assert len(hits) == 1
    assert "LoaderGraph" in hits[0].message


def test_ldt1601_flags_attribute_qualified_engines(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        from lance_distributed_training_tpu import fleet, service

        def build(addr, batch):
            a = service.client.RemoteLoader(addr, batch, 0, 1)
            b = fleet.balancer.FleetLoader(addr, batch, 0, 1)
            return a, b
    """}, hot_paths=["*"])
    assert [f.rule for f in findings
            if f.rule == "LDT1601"] == ["LDT1601", "LDT1601"]


def test_ldt1601_exempts_engine_home_modules(tmp_path):
    """data/pipeline.py + data/folder.py legitimately build inner engines,
    and data/graph.py is the one compile seam allowed to build all five."""
    src = """\
        def rebuild(ds, plan, decode):
            return DataPipeline(ds, plan, decode, None, 2)
    """
    findings = run_rules(tmp_path, {
        "data/pipeline.py": src,
        "data/folder.py": src,
        "data/graph.py": src,
        "service/client.py": src,
        "fleet/balancer.py": src,
    }, hot_paths=["*"])
    assert [f for f in findings if f.rule == "LDT1601"] == []


def test_ldt1601_silent_off_hot_paths(tmp_path):
    findings = run_rules(tmp_path, {"scripts/bench.py": """\
        def bench(ds, plan, decode):
            return MapStylePipeline(ds, 16, 0, 1, decode, None)
    """}, hot_paths=["trainer.py"])
    assert [f for f in findings if f.rule == "LDT1601"] == []


def test_ldt1601_loader_graph_composition_passes(tmp_path):
    findings = run_rules(tmp_path, {"m.py": """\
        from lance_distributed_training_tpu.data.graph import (
            Decode, InProcess, LanceSource, LoaderGraph,
        )

        def build(ds, decode):
            graph = LoaderGraph(
                LanceSource(ds, "batch", 16, 0, 1), Decode(decode),
                InProcess(),
            )
            graph.compile()
            return graph
    """}, hot_paths=["*"])
    assert [f for f in findings if f.rule == "LDT1601"] == []


def test_ldt1601_repo_hot_paths_are_graph_clean():
    """The repo's own hot-path modules compose graphs: the only engine
    constructions live in the exempt home modules + data/graph.py."""
    from lance_distributed_training_tpu.analysis.config import load_config

    config = load_config(str(REPO_ROOT))
    findings = analyze(str(REPO_ROOT), config)
    assert [f for f in findings if f.rule == "LDT1601"] == []


# -- LDT17xx device semantics (analysis/meshmodel.py) -------------------------


MESH_FIXTURE_ROOT = REPO_ROOT / "tests" / "fixtures" / "meshmodel"


def _mesh_config(**kwargs):
    """Neutralize every other family so mesh tests see only LDT17xx."""
    kwargs.setdefault("paths", ["."])
    kwargs.setdefault("queue_paths", [])
    kwargs.setdefault("content_paths", [])
    kwargs.setdefault("dispatch", {})
    kwargs.setdefault("resources", {})
    kwargs.setdefault("mesh_axes", ["data", "model"])
    kwargs.setdefault("static_funnels", ["quantize_*"])
    kwargs.setdefault("sync_funnels", [])
    kwargs.setdefault("device_hot_paths", [])
    return CheckConfig(**kwargs)


def run_mesh_rules(tmp_path, files, **config_kwargs):
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    return analyze(str(tmp_path), _mesh_config(**config_kwargs))


def test_ldt1701_flags_undeclared_axes(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        from jax.sharding import PartitionSpec as P
        from jax import lax

        def specs(x):
            a = P("data", None)
            b = P("modle")
            return lax.psum(x, "dta"), a, b
    """})
    bad = [f for f in findings if f.rule == "LDT1701"]
    assert sorted((f.line, f.message.split("'")[1]) for f in bad) == [
        (6, "modle"), (7, "dta"),
    ], [f.message for f in findings]


def test_ldt1701_declared_axes_and_nonliterals_clean(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        from jax.sharding import PartitionSpec as P
        from jax import lax

        def specs(x, axis):
            a = P("data", "model")
            b = P(("data", "model"))
            c = lax.pmean(x, axis_name="model")
            return lax.psum(x, axis), a, b, c
    """})
    assert [f for f in findings if f.rule == "LDT1701"] == []


def test_ldt1702_flags_read_after_donate(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        import jax

        def step(s, b):
            return s + b

        def loop(s, b):
            fn = jax.jit(step, donate_argnums=(0,))
            out = fn(s, b)
            return s + out
    """})
    bad = [f for f in findings if f.rule == "LDT1702"]
    assert [(f.line, f.message.split("'")[1]) for f in bad] == [(8, "s")]
    assert "read again at line 9" in bad[0].message


def test_ldt1702_rebind_is_clean(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        import jax

        def step(s, b):
            return s + b

        def loop(s, b):
            fn = jax.jit(step, donate_argnums=(0,))
            s = fn(s, b)
            return s
    """})
    assert [f for f in findings if f.rule == "LDT1702"] == []


def test_ldt1702_loop_carried_donation(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        import jax

        def step(s, b):
            return s + b

        def loop(s, batches):
            fn = jax.jit(step, donate_argnums=(0,))
            for b in batches:
                out = fn(s, b)
            return out
    """})
    bad = [f for f in findings if f.rule == "LDT1702"]
    assert len(bad) == 1 and bad[0].line == 9
    assert "re-read on the next loop iteration" in bad[0].message


def test_ldt1703_flags_shape_derived_static(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("rows",))
        def kernel(x, *, rows):
            return x[:rows]

        def call(batch):
            rows = batch.shape[0]
            return kernel(batch, rows=rows)
    """})
    bad = [f for f in findings if f.rule == "LDT1703"]
    assert [f.line for f in bad] == [10]
    assert "static argument 'rows'" in bad[0].message


def test_ldt1703_funneled_derivation_is_clean(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        from functools import partial
        import jax

        def quantize_rows(n):
            return ((n + 7) // 8) * 8

        @partial(jax.jit, static_argnames=("rows",))
        def kernel(x, *, rows):
            return x[:rows]

        def call(batch):
            rows = quantize_rows(batch.shape[0])
            return kernel(batch, rows=rows)
    """})
    assert [f for f in findings if f.rule == "LDT1703"] == []


def test_ldt1703_in_jit_shape_branch(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        import jax

        @jax.jit
        def f(x):
            if x.shape[0] > 4:
                return x * 2.0
            return x
    """}, content_paths=["m.py::f"])
    bad = [f for f in findings if f.rule == "LDT1703"]
    assert [f.line for f in bad] == [5]
    assert "Python branch on a parameter shape" in bad[0].message


def test_ldt1703_in_jit_branch_outside_content_paths_silent(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        import jax

        @jax.jit
        def f(x):
            if x.shape[0] > 4:
                return x * 2.0
            return x
    """})
    assert [f for f in findings if f.rule == "LDT1703"] == []


def test_ldt1704_flags_hot_path_sync(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        import jax.numpy as jnp

        def drain(x):
            val = jnp.sum(x)
            return float(val)
    """}, device_hot_paths=["m.py"])
    bad = [f for f in findings if f.rule == "LDT1704"]
    assert [f.line for f in bad] == [5]
    assert "float(val)" in bad[0].message


def test_ldt1704_sync_funnel_and_cold_module_silent(tmp_path):
    src = """\
        import jax.numpy as jnp

        def drain(x):
            val = jnp.sum(x)
            return float(val)
    """
    # Declared sync funnel: the drain is deliberate.
    findings = run_mesh_rules(
        tmp_path / "funnel", {"m.py": src},
        device_hot_paths=["m.py"], sync_funnels=["drain"],
    )
    assert [f for f in findings if f.rule == "LDT1704"] == []
    # Cold module: not on the declared device hot paths.
    findings = run_mesh_rules(tmp_path / "cold", {"m.py": src})
    assert [f for f in findings if f.rule == "LDT1704"] == []


def test_ldt1704_host_metadata_not_device_tainted(tmp_path):
    findings = run_mesh_rules(tmp_path, {"m.py": """\
        import numpy as np
        import jax

        def topology():
            devices = list(jax.devices())
            return np.array(devices).reshape(-1)
    """}, device_hot_paths=["m.py"])
    assert [f for f in findings if f.rule == "LDT1704"] == []


def test_ldt17xx_ignore_requires_reason(tmp_path):
    src = """\
        import jax.numpy as jnp

        def drain(x):
            val = jnp.sum(x)
            return float(val){comment}
    """
    # Bare ignore: stays live (the gate still fails).
    findings = run_mesh_rules(
        tmp_path / "bare",
        {"m.py": src.format(comment="  # ldt: ignore[LDT1704]")},
        device_hot_paths=["m.py"],
    )
    assert [f.rule for f in findings if f.rule == "LDT1704"] == ["LDT1704"]
    # Reasoned ignore: suppressed.
    findings = run_mesh_rules(
        tmp_path / "reasoned",
        {"m.py": src.format(
            comment="  # ldt: ignore[LDT1704] -- deliberate epoch drain"
        )},
        device_hot_paths=["m.py"],
    )
    assert [f for f in findings if f.rule == "LDT1704"] == []


def _meshmodel_fixture_config(**kwargs):
    kwargs.setdefault("paths", ["pkg"])
    kwargs.setdefault("content_paths", ["pkg/recompile.py::jit_branch"])
    kwargs.setdefault("protocol_module", "pkg/absent.py")
    kwargs.setdefault("static_funnels", ["quantize_rows"])
    kwargs.setdefault("sync_funnels", ["drain_ok"])
    kwargs.setdefault("device_hot_paths", ["pkg/hot.py"])
    return _mesh_config(**kwargs)


def test_meshmodel_fixture_yields_exactly_the_planted_findings():
    findings = analyze(str(MESH_FIXTURE_ROOT), _meshmodel_fixture_config())
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("LDT1701", "pkg/axes.py", 12),
        ("LDT1701", "pkg/axes.py", 20),
        ("LDT1702", "pkg/donate.py", 17),
        ("LDT1704", "pkg/hot.py", 9),
        ("LDT1703", "pkg/recompile.py", 20),
        ("LDT1703", "pkg/recompile.py", 30),
    ], [f"{f.rule} {f.location()}" for f in findings]


def test_compile_witness_prunes_steady_site():
    # kernel's def-site candidates are pkg/recompile.py:13 (decorator) and
    # :14 (def) — the runtime recorder reports co_firstlineno, which may be
    # either depending on the interpreter, so both join.
    config = _meshmodel_fixture_config()
    config.compile_witness = {"compiles": {
        "pkg/recompile.py:14": {"calls": 5, "compiles": 1, "post_warmup": 0},
    }, "transfers": {}}
    findings = analyze(str(MESH_FIXTURE_ROOT), config)
    call = next(f for f in findings
                if f.rule == "LDT1703" and f.line == 20)
    assert call.witness_pruned is True
    assert "witness_pruned" in call.message
    # The in-jit branch hazard keys a different jit site: stays live.
    branch = next(f for f in findings
                  if f.rule == "LDT1703" and f.line == 30)
    assert branch.witness_pruned is False


def test_compile_witness_reproduces_recompiling_site():
    config = _meshmodel_fixture_config()
    config.compile_witness = {"compiles": {
        "pkg/recompile.py:13": {"calls": 9, "compiles": 4, "post_warmup": 3},
    }, "transfers": {}}
    findings = analyze(str(MESH_FIXTURE_ROOT), config)
    call = next(f for f in findings
                if f.rule == "LDT1703" and f.line == 20)
    assert call.witness_pruned is False
    assert "recompiled after warmup" in call.message


def test_compile_witness_single_call_does_not_prune():
    # One call is warmup only: it cannot prove steady-state stability.
    config = _meshmodel_fixture_config()
    config.compile_witness = {"compiles": {
        "pkg/recompile.py:14": {"calls": 1, "compiles": 1, "post_warmup": 0},
    }, "transfers": {}}
    findings = analyze(str(MESH_FIXTURE_ROOT), config)
    call = next(f for f in findings
                if f.rule == "LDT1703" and f.line == 20)
    assert call.witness_pruned is False
    assert "witness" not in call.message


def test_compile_witness_untouched_site_changes_nothing():
    config = _meshmodel_fixture_config()
    config.compile_witness = {"compiles": {
        "pkg/other.py:1": {"calls": 50, "compiles": 1, "post_warmup": 0},
    }, "transfers": {}}
    findings = analyze(str(MESH_FIXTURE_ROOT), config)
    assert all(
        not f.witness_pruned and "witness" not in f.message
        for f in findings if f.rule == "LDT1703"
    )


def test_check_main_compile_witness_end_to_end(tmp_path):
    pytest.importorskip("tomli")
    site = str(MESH_FIXTURE_ROOT / "pkg" / "recompile.py") + ":14"
    witness = {
        "version": 1,
        "compiles": {site: {"calls": 5, "compiles": 1, "post_warmup": 0}},
        "transfers": {"h2d": {site: {"count": 2, "bytes": 4096}},
                      "d2h": {}},
    }
    wpath = tmp_path / "compile-witness.json"
    wpath.write_text(json.dumps(witness))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(MESH_FIXTURE_ROOT), "--json", "--no-baseline",
         "--compile-witness", str(wpath)],
        out=out,
    )
    assert rc == 1  # the other seeds still fail the gate
    data = json.loads(out.getvalue())
    pruned = next(f for f in data["findings"]
                  if f["rule"] == "LDT1703" and f["line"] == 20)
    assert pruned["witness_pruned"] is True
    assert pruned["rule_family"] == "mesh"
    live = next(f for f in data["findings"]
                if f["rule"] == "LDT1703" and f["line"] == 30)
    assert live["witness_pruned"] is False
    assert data["compile_witness"] == {
        "runtime_sites": 1, "matched_sites": 1, "recompiled_sites": 0,
        "h2d_events": 2, "d2h_events": 0,
    }


def test_check_main_compile_witness_text_summary(tmp_path):
    pytest.importorskip("tomli")
    site = str(MESH_FIXTURE_ROOT / "pkg" / "recompile.py") + ":13"
    wpath = tmp_path / "compile-witness.json"
    wpath.write_text(json.dumps({
        "version": 1,
        "compiles": {site: {"calls": 9, "compiles": 3, "post_warmup": 2}},
        "transfers": {"h2d": {}, "d2h": {site: {"count": 4, "bytes": 64}}},
    }))
    out = io.StringIO()
    rc = check_main(
        ["--root", str(MESH_FIXTURE_ROOT), "--no-baseline",
         "--compile-witness", str(wpath)],
        out=out,
    )
    assert rc == 1
    text = out.getvalue()
    assert ("compile witness: 1/1 runtime jit sites match static jit "
            "sites, 1 recompiled post-warmup, 0 H2D / 4 D2H transfer "
            "events") in text
    repro = [ln for ln in text.splitlines()
             if "LDT1703" in ln and "recompile.py:20" in ln]
    assert repro and "recompiled after warmup" in repro[0]


def test_check_main_unreadable_compile_witness_is_usage_error(tmp_path):
    pytest.importorskip("tomli")
    wpath = tmp_path / "torn.json"
    wpath.write_text("{not json")
    out = io.StringIO()
    rc = check_main(
        ["--root", str(MESH_FIXTURE_ROOT), "--no-baseline",
         "--compile-witness", str(wpath)],
        out=out,
    )
    assert rc == 2
    assert "unreadable compile witness" in out.getvalue()


def test_mesh_model_is_shared_per_run(monkeypatch):
    """One ProgramInfo parse pass, one MeshModel build, shared by all four
    LDT17xx rules — the same single-build contract as the other models."""
    import lance_distributed_training_tpu.analysis.meshmodel as mm

    calls = {"n": 0}
    real_init = mm.MeshModel.__init__

    def counting_init(self, program, config):
        calls["n"] += 1
        real_init(self, program, config)

    monkeypatch.setattr(mm.MeshModel, "__init__", counting_init)
    analyze(str(MESH_FIXTURE_ROOT), _meshmodel_fixture_config())
    assert calls["n"] == 1


def test_repo_mesh_model_sees_known_jit_topology():
    """The real tree: the mesh model resolves the trainer's donating train
    step, the device kernels' static arguments, and only declared axes."""
    from lance_distributed_training_tpu.analysis.concmodel import (
        build_program,
    )
    from lance_distributed_training_tpu.analysis.config import load_config
    from lance_distributed_training_tpu.analysis.core import parse_modules
    from lance_distributed_training_tpu.analysis.meshmodel import (
        build_mesh_model,
    )

    config = load_config(str(REPO_ROOT))
    modules, _findings, _n = parse_modules(str(REPO_ROOT), config)
    program = build_program(modules, config)
    mesh = build_mesh_model(program, config)
    by_name = {}
    for site in mesh.jit_sites:
        by_name.setdefault(site.name, site)
    # The donating train step (trainer.make_train_step).
    step = by_name["step"]
    assert step.module == "lance_distributed_training_tpu/trainer.py"
    assert 0 in step.donate_argnums and step.donate_conditional
    # The device decode kernel's static output size.
    decode = by_name["decode_coeff_batch"]
    assert decode.static_argnames == ("out_size",)
    # The token pack kernel's static geometry.
    pack = by_name["pack_token_batch"]
    assert set(pack.static_argnames) == {"rows", "pack_len"}
    # Every literal axis reference is in the declared vocabulary.
    declared = set(mesh.mesh_axes)
    assert declared == {"data", "model", "seq", "pipe"}
    assert {r.axis for r in mesh.axis_refs} <= declared


# -- runtime compile sanitizer (utils/compiletrack.py) ------------------------


@pytest.fixture()
def compiletrack_sandbox():
    """Snapshot/restore the recorder around tests that enable or reset it
    (a sanitizer-enabled tier-1 session collects its witness ACROSS the
    suite — same discipline as leaktrack_sandbox)."""
    from lance_distributed_training_tpu.utils import compiletrack

    saved = compiletrack.snapshot()
    compiletrack.disable()
    compiletrack.reset()
    try:
        yield compiletrack
    finally:
        compiletrack.restore(saved)


def test_compiletrack_counts_warmup_and_recompiles(compiletrack_sandbox):
    import numpy as np

    ct = compiletrack_sandbox
    ct.enable()

    def kernel(x, scale=1.0):
        return x

    wrapped = ct.wrap_jit(kernel)
    site = wrapped.__ldt_compile_site__
    assert site.endswith(f":{kernel.__code__.co_firstlineno}")
    wrapped(np.zeros((4, 4), dtype=np.float32))
    wrapped(np.ones((4, 4), dtype=np.float32))  # same abstract signature
    assert ct.sites()[site] == {
        "calls": 2, "compiles": 1, "post_warmup": 0,
    }
    wrapped(np.zeros((8, 4), dtype=np.float32))  # new shape after warmup
    assert ct.sites()[site] == {
        "calls": 3, "compiles": 2, "post_warmup": 1,
    }
    # A changed static Python scalar is a retrace too.
    wrapped(np.zeros((4, 4), dtype=np.float32), scale=2.0)
    assert ct.sites()[site]["post_warmup"] == 2


def test_compiletrack_disabled_records_nothing(compiletrack_sandbox):
    ct = compiletrack_sandbox

    def kernel(x):
        return x

    wrapped = ct.wrap_jit(kernel)
    wrapped(1)
    assert ct.sites() == {}


def test_compiletrack_recovers_def_site_through_jax_jit(
    compiletrack_sandbox,
):
    import jax
    import jax.numpy as jnp

    ct = compiletrack_sandbox
    ct.enable()

    def double(x):
        return x * 2

    wrapped = ct.wrap_jit(jax.jit(double))
    site = wrapped.__ldt_compile_site__
    assert site.endswith(f":{double.__code__.co_firstlineno}")
    out = wrapped(jnp.ones((2,), jnp.float32))
    assert float(out[0]) == 2.0
    assert ct.sites()[site]["calls"] == 1


def test_compiletrack_transfer_counters(compiletrack_sandbox):
    ct = compiletrack_sandbox
    ct.enable()
    for _ in range(2):
        ct.track_transfer("h2d", 1024)
    ct.track_transfer("d2h", 16)
    ((h2d_site, h2d),) = ct.transfers()["h2d"].items()
    assert "test_analysis.py" in h2d_site
    assert h2d == {"count": 2, "bytes": 2048}
    ((_, d2h),) = ct.transfers()["d2h"].items()
    assert d2h == {"count": 1, "bytes": 16}


def test_compiletrack_dump_roundtrips_through_witness_loader(
    compiletrack_sandbox, tmp_path
):
    from lance_distributed_training_tpu.analysis.cli import (
        load_compile_witness,
    )

    ct = compiletrack_sandbox
    ct.enable()

    def kernel(n):
        return n

    wrapped = ct.wrap_jit(kernel)
    wrapped(3)
    wrapped(3)
    wrapped(4)  # plain-value signature change: a post-warmup retrace
    ct.track_transfer("d2h", 64)
    path = ct.dump(str(tmp_path / "witness.json"))
    witness = load_compile_witness(path, str(REPO_ROOT / "tests"))
    ((site, entry),) = witness["compiles"].items()
    assert site.startswith("test_analysis.py:")
    assert entry == {"calls": 3, "compiles": 2, "post_warmup": 1}
    ((_, d2h),) = witness["transfers"]["d2h"].items()
    assert d2h == {"count": 1, "bytes": 64}


# -- ldt graph --mesh ---------------------------------------------------------


def test_graph_mesh_text_smoke():
    pytest.importorskip("tomli")
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(
        ["--root", str(MESH_FIXTURE_ROOT), "pkg", "--mesh"], out=out
    )
    assert rc == 0
    text = out.getvalue()
    assert "mesh model:" in text
    assert "jit kernel" in text and "static: rows" in text
    assert "jit step" in text and "donate: #0" in text
    assert "axis dta [UNDECLARED]" in text


def test_graph_mesh_dot_smoke():
    pytest.importorskip("tomli")
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(
        ["--root", str(MESH_FIXTURE_ROOT), "pkg", "--mesh", "--dot"],
        out=out,
    )
    assert rc == 0
    dot = out.getvalue()
    assert "shape=doubleoctagon" in dot
    assert '"axis:dta"' in dot and '"axis:data"' in dot


def test_graph_mesh_cli_dispatch():
    pytest.importorskip("tomli")
    import lance_distributed_training_tpu.cli as cli

    rc = cli.main(["graph", "--root", str(MESH_FIXTURE_ROOT), "pkg",
                   "--mesh"])
    assert rc == 0
