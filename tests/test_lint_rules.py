"""Per-rule positive/negative pins for the kgct-lint rule suite.

Every rule must (a) fire on a minimal violating snippet — the regression
the rule exists to catch — and (b) stay silent on the idiomatic-correct
form the engine actually uses. The empty-baseline run over the real
package is tests/test_lint_clean.py; these are the rule semantics.
"""

import textwrap
from pathlib import Path

import pytest

from kubernetes_gpu_cluster_tpu.analysis.core import LintModule, run_lint
from kubernetes_gpu_cluster_tpu.analysis.rules import ALL_RULES, rules_by_code


def lint(code: str, rule_code: str, relpath: str = "engine/fake.py"):
    mod = LintModule(Path(relpath), source=textwrap.dedent(code))
    [rule] = rules_by_code([rule_code])
    return list(rule.check(mod))


class TestTraceSafety:  # KGCT001
    def test_python_if_on_traced_arg_fires(self):
        found = lint("""
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """, "KGCT001")
        assert len(found) == 1 and "if" in found[0].message

    def test_taint_propagates_through_assignment(self):
        found = lint("""
            import jax

            @jax.jit
            def f(x):
                y = x + 1
                while y < 3:
                    y = y + 1
                return y
        """, "KGCT001")
        assert found and "while" in found[0].message

    def test_builder_maybe_jit_pattern_is_analyzed(self):
        found = lint("""
            class FooEngine:
                def _build_step(self):
                    def step(params, kv, flags):
                        return kv if bool(flags) else params
                    return self._maybe_jit(step, donate_argnums=(1,))
        """, "KGCT001")
        # both the conditional expression and the bool() call flag
        assert found and any("bool()" in f.message for f in found)

    def test_shape_len_and_static_argnames_stay_silent(self):
        assert lint("""
            import jax

            def build():
                def step(x, mode):
                    n = x.shape[0]
                    m = n if n % 2 == 0 else 1
                    if mode == "greedy":
                        return x.reshape(m, -1)
                    if len(x) > 4:
                        return x * 2
                    return x
                return jax.jit(step, static_argnames=("mode",))
        """, "KGCT001") == []


class TestHostSync:  # KGCT002
    def test_item_in_hot_path_fires(self):
        found = lint("""
            class FooEngine:
                def step(self):
                    out = self._decode_fn(1)
                    return out.item()
        """, "KGCT002")
        assert len(found) == 1 and ".item()" in found[0].message

    def test_reachability_through_self_calls(self):
        found = lint("""
            class FooEngine:
                def _step(self):
                    return self._helper()

                def _helper(self):
                    x = self._decode_fn(1)
                    x.block_until_ready()
                    return x
        """, "KGCT002")
        assert found and "block_until_ready" in found[0].message

    def test_implicit_float_on_step_output_fires(self):
        found = lint("""
            class FooEngine:
                def _step(self):
                    out = self._decode_fn(1)
                    return float(out)
        """, "KGCT002")
        assert found and "float()" in found[0].message

    def test_device_fetch_window_is_sanctioned(self):
        assert lint("""
            class FooEngine:
                def _step(self):
                    out = self._decode_fn(1)
                    with ph("device_fetch"):
                        out.block_until_ready()
                    return out
        """, "KGCT002") == []

    def test_off_hot_path_sync_is_fine(self):
        # probe/bench code outside step reachability may sync freely
        assert lint("""
            class FooEngine:
                def probe(self):
                    self._decode_fn(1).block_until_ready()
        """, "KGCT002") == []


class TestRecompileRisk:  # KGCT003
    def test_jit_in_loop_fires(self):
        found = lint("""
            import jax

            def bench(xs):
                for x in xs:
                    f = jax.jit(lambda a: a + 1)
                    f(x)
        """, "KGCT003")
        assert found and "loop" in found[0].message

    def test_jit_in_hot_path_fires(self):
        found = lint("""
            import jax

            class FooEngine:
                def _step(self, fn, x):
                    return jax.jit(fn)(x)
        """, "KGCT003")
        assert found and "hot-path" in found[0].message

    def test_unbucketed_len_shape_fires(self):
        found = lint("""
            import numpy as np

            class FooEngine:
                def _step(self, seqs):
                    return self._decode_fn(np.zeros((len(seqs), 4)))
        """, "KGCT003")
        assert found and "bucket" in found[0].message

    def test_bucketed_len_and_init_builders_stay_silent(self):
        assert lint("""
            import jax
            import numpy as np

            class FooEngine:
                def _build_decode_fn(self):
                    def step(x):
                        return x
                    return jax.jit(step)

                def _step(self, seqs):
                    B = _bucket(len(seqs), self.buckets)
                    return self._decode_fn(np.zeros((B, 4)))
        """, "KGCT003") == []


class TestDonationSafety:  # KGCT004
    def test_read_after_donation_fires(self):
        found = lint("""
            import jax

            class FooEngine:
                def __init__(self, step):
                    self._step_fn = jax.jit(step, donate_argnums=(1,))

                def run(self, params, kv):
                    out = self._step_fn(params, kv)
                    return out, kv.sum()
        """, "KGCT004")
        assert len(found) == 1 and "donated buffer 'kv'" in found[0].message

    def test_rebound_in_call_statement_is_safe(self):
        assert lint("""
            import jax

            class FooEngine:
                def __init__(self, step):
                    self._step_fn = jax.jit(step, donate_argnums=(1,))

                def run(self, params):
                    out, self.kv = self._step_fn(params, self.kv)
                    return out, self.kv.sum()
        """, "KGCT004") == []

    def test_builder_indirection_is_resolved(self):
        found = lint("""
            class FooEngine:
                def __init__(self):
                    self._step_fn = self._build()

                def _build(self):
                    def step(params, kv):
                        return kv
                    return self._maybe_jit(step, donate_argnums=(1,))

                def run(self, params, kv):
                    out = self._step_fn(params, kv)
                    norm = kv.mean()
                    return out, norm
        """, "KGCT004")
        assert found and "read after dispatch" in found[0].message


class TestKVCommitSafety:  # KGCT005
    def test_naked_slot_math_fires(self):
        found = lint("""
            def compute_slot(page, ps, pos):
                return page * ps + pos % ps
        """, "KGCT005", relpath="engine/spec/fake.py")
        assert len(found) == 1 and "slot expression" in found[0].message

    def test_scrap_page_guard_is_enough(self):
        assert lint("""
            def compute_slot(page, ps, pos, max_len):
                if pos >= max_len:
                    return SCRAP_PAGE * ps + pos % ps
                return page * ps + pos % ps
        """, "KGCT005", relpath="engine/spec/fake.py") == []

    def test_committed_anchor_is_enough(self):
        assert lint("""
            def fill_row(seq, slot_mapping, ps):
                pos = seq.num_tokens - 1
                slot_mapping[0] = seq.pages[pos // ps] * ps + pos % ps
        """, "KGCT005", relpath="engine/fake.py") == []

    def test_out_of_scope_modules_ignored(self):
        assert lint("""
            def compute_slot(page, ps, pos):
                return page * ps + pos % ps
        """, "KGCT005", relpath="serving/fake.py") == []


class TestAsyncioHygiene:  # KGCT006
    def test_time_sleep_in_async_fires(self):
        found = lint("""
            import time

            async def handler(request):
                time.sleep(0.5)
        """, "KGCT006")
        assert found and "time.sleep" in found[0].message

    def test_get_event_loop_fires_anywhere(self):
        found = lint("""
            import asyncio

            def start(self):
                self._loop = asyncio.get_event_loop()
        """, "KGCT006")
        assert found and "get_running_loop" in found[0].message

    def test_sync_context_and_async_sleep_are_fine(self):
        assert lint("""
            import asyncio
            import time

            def worker():
                time.sleep(0.5)

            async def handler(request):
                await asyncio.sleep(0.5)
                loop = asyncio.get_running_loop()
        """, "KGCT006") == []


class TestMetricHygiene:  # KGCT007
    def test_request_scope_construction_fires(self):
        found = lint("""
            async def handler(request):
                h = Histogram("kgct_x_seconds")
                h.observe(1.0)
        """, "KGCT007")
        assert found and "process-lifetime" in found[0].message

    def test_unbounded_label_value_fires(self):
        found = lint("""
            def on_finish(self, seq):
                self.ttft.observe(0.5, (seq.request_id,))
        """, "KGCT007")
        assert found and "unbounded" in found[0].message

    def test_fstring_label_fires(self):
        found = lint("""
            def on_finish(self, seq, code):
                self.ttft.observe(0.5, (f"status-{code}",))
        """, "KGCT007")
        assert found and "unbounded" in found[0].message

    def test_init_construction_and_bounded_labels_are_fine(self):
        assert lint("""
            class Obs:
                def __init__(self):
                    self.ttft = Histogram("kgct_ttft_seconds",
                                          labels=("outcome",))

                def on_finish(self, seq, outcome):
                    self.ttft.observe(0.5, (_outcome(seq, None),))
        """, "KGCT007") == []


class TestLoggingHygiene:  # KGCT008
    def test_fstring_log_fires(self):
        found = lint("""
            def step(logger, arr):
                logger.info(f"step done: {arr}")
        """, "KGCT008")
        assert found and "f-string" in found[0].message

    def test_eager_percent_and_format_fire(self):
        found = lint("""
            def step(logger, arr):
                logger.debug("x: %s" % arr)
                logger.warning("y: {}".format(arr))
        """, "KGCT008")
        assert len(found) == 2

    def test_lazy_template_is_fine(self):
        assert lint("""
            def step(logger, arr):
                logger.info("step done: %s tokens", arr)
        """, "KGCT008") == []


class TestQuantSurface:  # KGCT009
    def test_direct_matmul_on_quant_key_fires(self):
        found = lint("""
            import jax.numpy as jnp

            def attn(x, lp):
                return jnp.dot(x, lp["wq"], preferred_element_type=None)
        """, "KGCT009", relpath="models/fake.py")
        assert len(found) == 1 and "_dot" in found[0].message

    def test_matmul_operator_spelling_fires(self):
        found = lint("""
            def attn(x, lp):
                return x @ lp["wo"]
        """, "KGCT009", relpath="models/fake.py")
        assert len(found) == 1 and "matmul" in found[0].message

    def test_astype_dequant_copy_fires(self):
        found = lint("""
            import jax.numpy as jnp

            def upload(lp, dtype):
                return lp["w_down"].astype(dtype)
        """, "KGCT009", relpath="models/fake.py")
        assert len(found) == 1 and "dequantizes" in found[0].message

    def test_sanctioned_dot_helper_is_silent(self):
        assert lint("""
            import jax.numpy as jnp

            def _dot(x, lp, name):
                w = lp[name]
                if w.dtype == jnp.int8:
                    return jnp.dot(x, w.astype(x.dtype)) * lp[name + "_scale"]
                return jnp.dot(x, w)

            def attn(x, lp):
                return _dot(x, lp, "wq")
        """, "KGCT009", relpath="models/fake.py") == []

    def test_non_quant_keys_and_other_modules_silent(self):
        code = """
            import jax.numpy as jnp

            def route(x, lp):
                return jnp.dot(x, lp["router"])
        """
        assert lint(code, "KGCT009", relpath="models/fake.py") == []
        # outside models/: out of scope entirely
        assert lint("""
            import jax.numpy as jnp

            def f(x, lp):
                return jnp.dot(x, lp["wq"])
        """, "KGCT009", relpath="engine/fake.py") == []

    def test_key_literal_drift_fires(self):
        found = lint("""
            QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                                "w_down", "router")
        """, "KGCT009", relpath="ops/quant.py")
        assert len(found) == 1 and "drifted" in found[0].message

    def test_real_surface_is_in_sync(self):
        """The shipped ops/quant.py literal matches the rule's pin (the
        tier-1 empty-baseline run enforces this too; this pin keeps the
        failure local and explicit)."""
        root = Path(__file__).resolve().parent.parent
        mod = LintModule(
            root / "kubernetes_gpu_cluster_tpu" / "ops" / "quant.py",
            root=root / "kubernetes_gpu_cluster_tpu")
        [rule] = rules_by_code(["KGCT009"])
        assert list(rule.check(mod)) == []


class TestSwapOrder:  # KGCT010
    def test_release_before_gather_fires(self):
        found = lint("""
            def preempt(self, victim):
                self._release(victim)
                pages = self.swapper.swap_out(victim.pages)
                victim.host_pages = pages
        """, "KGCT010")
        assert len(found) == 1 and "before the swap gather" in found[0].message

    def test_allocator_free_before_spill_fires(self):
        found = lint("""
            def evict(self, page):
                self.allocator.free([page])
                return self.swapper.spill_page(page)
        """, "KGCT010")
        assert len(found) == 1

    def test_gather_then_release_is_silent(self):
        assert lint("""
            def preempt(self, victim):
                pages = self.swapper.swap_out(victim.pages)
                self._release(victim)
                victim.host_pages = pages
        """, "KGCT010") == []

    def test_release_only_and_host_free_silent(self):
        # abort/finish paths release without gathering — out of scope
        assert lint("""
            def abort(self, seq):
                self._release(seq)
        """, "KGCT010") == []
        # host-pool frees are not device releases
        assert lint("""
            def drop(self, page, hp):
                self.swapper.free_host([hp])
                return self.swapper.spill_page(page)
        """, "KGCT010") == []

    def test_outside_engine_out_of_scope(self):
        assert lint("""
            def preempt(self, victim):
                self._release(victim)
                return self.swapper.swap_out(victim.pages)
        """, "KGCT010", relpath="serving/fake.py") == []


class TestRouterPickPath:  # KGCT011
    def test_min_over_replicas_outside_pick_fires(self):
        found = lint("""
            class Router:
                def proxy(self, request):
                    replica = min(self.replicas, key=lambda r: r.inflight)
                    return replica
        """, "KGCT011", relpath="serving/fake.py")
        assert len(found) == 1 and "_pick seam" in found[0].message

    def test_sorted_inflight_selection_fires(self):
        found = lint("""
            def rebalance(self, healthy):
                return sorted(healthy, key=lambda r: r.inflight)[0]
        """, "KGCT011", relpath="serving/fake.py")
        assert len(found) == 1

    def test_random_choice_from_replicas_fires(self):
        found = lint("""
            import random

            def desperate(self):
                return random.choice(self.replicas)
        """, "KGCT011", relpath="serving/fake.py")
        assert len(found) == 1

    def test_inflight_mutation_outside_proxy_fires(self):
        found = lint("""
            def metrics(self, replica):
                replica.inflight = 0
                return replica
        """, "KGCT011", relpath="serving/fake.py")
        assert len(found) == 1 and "accounting pair" in found[0].message

    def test_pick_and_proxy_accounting_are_sanctioned(self):
        assert lint("""
            class Router:
                def _pick(self, exclude=None):
                    healthy = [r for r in self.replicas if r.healthy]
                    least = min(r.inflight for r in healthy)
                    tied = [r for r in healthy if r.inflight == least]
                    return tied[0]

                def proxy(self, request):
                    replica = self._pick()
                    replica.inflight += 1
                    try:
                        return self.forward(replica, request)
                    finally:
                        replica.inflight -= 1
        """, "KGCT011", relpath="serving/fake.py") == []

    def test_reads_and_init_stay_silent(self):
        # health/metrics ITERATE and read the load signal — not selection.
        assert lint("""
            class Replica:
                def __init__(self, url):
                    self.inflight = 0

            class Router:
                def health(self, request):
                    return {r.url: r.inflight for r in self.replicas}

                def metrics(self, request):
                    total = sum(r.inflight for r in self.replicas)
                    return total
        """, "KGCT011", relpath="serving/fake.py") == []

    def test_outside_serving_out_of_scope(self):
        assert lint("""
            def schedule(self):
                victim = min(self.replicas, key=lambda r: r.inflight)
                victim.inflight += 1
        """, "KGCT011", relpath="engine/fake.py") == []


class TestTraceEmitHygiene:  # KGCT012
    def test_file_io_in_emit_fires(self):
        found = lint("""
            class RequestTracer:
                def emit(self, kind, request_id=""):
                    with open("/tmp/trace.log", "a") as f:
                        f.write(kind)
        """, "KGCT012", relpath="observability/fake.py")
        assert found and any("open()" in f.message for f in found)

    def test_serialization_and_lock_in_record_fire(self):
        found = lint("""
            import json

            class FlightRecorder:
                def record(self, kind, request_id="", args=None):
                    with self._lock:
                        self._ring.append(json.dumps(args))
        """, "KGCT012", relpath="observability/fake.py")
        msgs = " ".join(f.message for f in found)
        assert "json.dumps" in msgs and "lock held" in msgs

    def test_host_sync_in_snapshot_fires(self):
        found = lint("""
            class FlightRecorder:
                def maybe_snapshot(self):
                    self._ring.append(self._occupancy.item())
        """, "KGCT012", relpath="observability/fake.py")
        assert len(found) == 1 and ".item()" in found[0].message

    def test_dump_in_engine_hot_path_fires(self):
        found = lint("""
            class FooEngine:
                def step(self):
                    outs = self._run()
                    self.obs.flight.dump("per_step")
                    return outs

                def _run(self):
                    return []
        """, "KGCT012", relpath="engine/fake.py")
        assert len(found) == 1 and "hot-path" in found[0].message

    def test_export_in_router_proxy_fires(self):
        found = lint("""
            class Router:
                async def proxy(self, request):
                    doc = self.tracer.export_perfetto()
                    return doc
        """, "KGCT012", relpath="serving/fake.py")
        assert len(found) == 1 and "export" in found[0].message

    def test_awaited_emit_in_serving_fires(self):
        found = lint("""
            class Router:
                async def proxy(self, request):
                    await self.tracer.emit("arrival", "r1")
        """, "KGCT012", relpath="serving/fake.py")
        assert len(found) == 1 and "synchronous" in found[0].message

    def test_append_only_writes_and_offline_dump_are_silent(self):
        # The shipped shape: emit/record are pure appends; dump/export live
        # on failure handlers and debug endpoints, off the hot path.
        assert lint("""
            import time

            class RequestTracer:
                def emit(self, kind, request_id="", **args):
                    rec = self.recorder
                    if rec is not None:
                        rec.record(kind, request_id, args)
                    self._ring.append((time.monotonic(), kind, args))

            class FlightRecorder:
                def record(self, kind, request_id="", args=None):
                    self._ring.append((time.monotonic(), kind, args))

                def maybe_snapshot(self):
                    self._ring.append(self._source())

                def dump(self, reason):
                    with open("/tmp/x.json", "w") as f:
                        f.write(reason)
        """, "KGCT012", relpath="observability/fake.py") == []

    def test_emit_on_hot_path_and_dump_off_it_are_silent(self):
        # Emitting from step IS the design; dump from a non-step method
        # (failure handler) is the sanctioned place for I/O.
        assert lint("""
            class FooEngine:
                def step(self):
                    self.obs.tracer.emit("decode", "", batch=4)
                    return []

                def on_fatal(self, err):
                    self.obs.flight.dump("fatal", error=str(err))
        """, "KGCT012", relpath="engine/fake.py") == []

    def test_outside_scopes_silent(self):
        # dump on a non-proxy serving handler (debug endpoint): fine.
        assert lint("""
            class Router:
                async def debug_flightrecorder(self, request):
                    return self.flight.export()
        """, "KGCT012", relpath="serving/fake.py") == []
        # unrelated .dump() with no tracer/recorder receiver: out of scope.
        assert lint("""
            class FooEngine:
                def step(self):
                    return self.checkpointer.dump("state")
        """, "KGCT012", relpath="engine/fake.py") == []


class TestFramework:
    def test_every_rule_has_code_name_description(self):
        codes = [r.code for r in ALL_RULES]
        assert len(codes) == len(set(codes)) and len(codes) >= 8
        for rule in ALL_RULES:
            assert rule.code.startswith("KGCT")
            assert rule.name and rule.description

    def test_unknown_select_raises(self):
        with pytest.raises(ValueError, match="unknown rule"):
            rules_by_code(["KGCT999"])

    def test_syntax_error_is_a_finding_not_a_crash(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def broken(:\n")
        findings = run_lint([bad])
        assert len(findings) == 1 and findings[0].rule == "KGCT000"

    def test_findings_sorted_and_formatted(self, tmp_path):
        f = tmp_path / "two.py"
        f.write_text(textwrap.dedent("""
            import time

            async def b(logger, arr):
                time.sleep(1)
                logger.info(f"x {arr}")
        """))
        findings = run_lint([f], root=tmp_path)
        assert [x.rule for x in findings] == ["KGCT006", "KGCT008"]
        assert findings[0].format().startswith("two.py:")


class TestKVBoundary:  # KGCT013
    def test_np_asarray_of_kv_pool_fires(self):
        found = lint("""
            import numpy as np

            class Engine:
                def leak(self, pages):
                    return np.asarray(self.kv_cache.k[:, pages])
        """, "KGCT013", relpath="engine/engine.py")
        assert len(found) == 1 and "sanctioned" in found[0].message

    def test_device_get_of_kv_fires_in_serving(self):
        found = lint("""
            import jax

            def ship(kv):
                return jax.device_get(kv.k)
        """, "KGCT013", relpath="serving/api_server.py")
        assert len(found) == 1

    def test_kv_cache_module_is_the_sanctioned_seam(self):
        """The seam's own gather (np.asarray of the fetched KV inside
        kv_cache.py) is exempt — it IS the sanctioned path."""
        assert lint("""
            import numpy as np

            class KVPageIO:
                def export_pages(self, pages):
                    k_g, v_g = self._gather_fn(self.kv.k, self.kv.v, pages)
                    return np.asarray(k_g), np.asarray(v_g)
        """, "KGCT013", relpath="engine/kv_cache.py") == []

    def test_non_kv_fetches_stay_silent(self):
        assert lint("""
            import numpy as np

            def fine(batch, next_tokens, seq):
                a = np.asarray(next_tokens)
                b = np.asarray(batch.tokens)
                c = np.asarray(seq.pages, np.int64)
                return a, b, c
        """, "KGCT013", relpath="engine/engine.py") == []


class TestSwapOrderExportCoverage:  # KGCT010 extension
    def test_free_before_export_gather_fires(self):
        found = lint("""
            class Engine:
                def export_held(self, seq):
                    self.scheduler.allocator.free(seq.pages)
                    return self.kv_io.export_pages(seq.pages)
        """, "KGCT010", relpath="engine/engine.py")
        assert len(found) == 1 and "before" in found[0].message

    def test_gather_then_free_is_clean(self):
        assert lint("""
            class Engine:
                def export_held(self, seq):
                    k, v = self.kv_io.export_pages(seq.pages)
                    self.scheduler.allocator.free(seq.pages)
                    return k, v
        """, "KGCT010", relpath="engine/engine.py") == []


class TestMigrationStateSafety:  # KGCT014
    def test_inflight_window_in_returned_dict_fires(self):
        """The regression the rule exists to catch: window speculation
        (sampled-but-unfetched device tokens) serialized into the
        cross-replica state — a peer importing it forks the stream from
        history this engine never committed."""
        found = lint("""
            class Engine:
                def export_running(self, seq):
                    return {
                        "output_token_ids": list(seq.output_token_ids)
                        + list(self._inflight["toks"]),
                        "k": self.kv_io.export_pages(seq.pages),
                    }
        """, "KGCT014", relpath="engine/engine.py")
        assert len(found) == 1 and "_inflight" in found[0].message
        assert "committed" in found[0].message

    def test_window_scratch_store_into_state_fires(self):
        found = lint("""
            class Engine:
                def _export_state(self, seq, k_np, v_np):
                    state = {"k": k_np, "v": v_np}
                    state["logprobs"] = self._window_scratch.float_b
                    return state
        """, "KGCT014", relpath="engine/engine.py")
        assert len(found) == 1 and "float_b" in found[0].message

    def test_zombie_set_via_update_fires(self):
        found = lint("""
            class Engine:
                def export_running(self, seq):
                    state = {}
                    state.update(pending=self._inflight["zombies"])
                    return state
        """, "KGCT014", relpath="engine/engine.py")
        assert len(found) == 1

    @pytest.mark.parametrize("source", [
        "seq.num_tokens + seq.inflight_tokens", "seq.sched_tokens",
        "seq.inflight_row"])
    def test_tokens_in_flight_of_a_sequence_fire(self, source):
        """The device queue's per-sequence bookkeeping: what a sequence
        WILL hold once the step in flight is fetched is no committed
        quantity either, wherever it is kept."""
        found = lint(f"""
            class Engine:
                def _export_state(self, seq, k_np, v_np):
                    state = {{"k": k_np, "v": v_np}}
                    state["num_tokens"] = {source}
                    return state
        """, "KGCT014", relpath="engine/engine.py")
        assert len(found) == 1 and "committed" in found[0].message

    def test_committed_only_export_with_zombie_bookkeeping_silent(self):
        """The idiomatic export: committed host history + fetched buffers
        into the state; the in-flight window touched ONLY for retirement
        bookkeeping (zombie registration, deferred release) — data and
        bookkeeping must be distinguished or the real export can never
        pass its own rule."""
        assert lint("""
            class Engine:
                def export_running(self, seq):
                    k_np, v_np = self.kv_io.export_pages(seq.pages)
                    state = {
                        "prompt_token_ids": list(seq.prompt_token_ids),
                        "output_token_ids": list(seq.output_token_ids),
                        "output_logprobs": list(seq.output_logprobs),
                        "k": k_np, "v": v_np,
                    }
                    state["mid_stream"] = True
                    if self._inflight is not None:
                        self._inflight["zombies"].add(seq.request_id)
                        self._deferred_release.append(seq)
                    return state
        """, "KGCT014", relpath="engine/engine.py") == []

    def test_non_export_functions_silent(self):
        assert lint("""
            class Engine:
                def step(self):
                    toks = self._inflight["window_toks"]
                    return {"window": toks}
        """, "KGCT014", relpath="engine/engine.py") == []

    def test_outside_engine_scope_silent(self):
        assert lint("""
            def export_running(seq, inflight):
                return {"toks": inflight["window_toks"]}
        """, "KGCT014", relpath="serving/api_server.py") == []


class TestTenantAccountingSafety:  # KGCT015
    def test_serving_layer_charge_fires(self):
        """The regression the rule exists to catch: a serving handler
        charging a tier's fairness clock 'to help' a tenant — every
        subsequent weighted-fair decision is then skewed for the life of
        the process."""
        found = lint("""
            class APIServer:
                async def _run(self, request, tier):
                    self.engine.scheduler.qos.charge(tier, 512)
        """, "KGCT015", relpath="serving/api_server.py")
        assert len(found) == 1 and "fair-share seam" in found[0].message

    def test_direct_clock_write_outside_qos_fires(self):
        found = lint("""
            def rebalance(qos):
                qos.virtual_tokens["batch"] += 100.0
        """, "KGCT015", relpath="engine/engine.py")
        assert len(found) == 1 and "virtual_tokens" in found[0].message

    def test_sync_active_from_bench_fires(self):
        found = lint("""
            def warm(engine):
                engine.scheduler.qos.sync_active(["interactive"])
        """, "KGCT015", relpath="observability/__init__.py")
        assert len(found) == 1

    def test_scheduler_seam_charge_silent(self):
        assert lint("""
            class Scheduler:
                def _qos_charge_batch(self, batch):
                    for seq in batch.seqs:
                        self.qos.charge(seq.params.qos_tier, 8)
        """, "KGCT015", relpath="engine/scheduler.py") == []

    def test_mixed_batch_seam_silent(self):
        assert lint("""
            def build_mixed_batch(sched):
                sched.qos.charge("batch", 1)
        """, "KGCT015", relpath="engine/mixed_batch.py") == []

    def test_clock_write_inside_qos_module_silent(self):
        assert lint("""
            class QoSAccounting:
                def charge(self, name, tokens):
                    self.virtual_tokens[name] += tokens / 2.0
                    self.served_tokens[name] += tokens
        """, "KGCT015", relpath="engine/qos.py") == []

    def test_reads_and_other_accounting_silent(self):
        """Snapshot READS and the serving-side admission ledger
        (tier_inflight — a different mechanism with its own accounting
        pair) stay silent."""
        assert lint("""
            def render(qos, adm):
                vt = dict(qos.virtual_tokens)
                adm.tier_inflight["batch"] += 1
                return vt
        """, "KGCT015", relpath="serving/metrics.py") == []


class TestFleetFetchBoundary:  # KGCT016
    def test_handler_side_import_fires(self):
        """A serving handler calling an import seam directly on the event
        loop — the scatter would race the step loop against the donated
        pool."""
        found = lint("""
            class Handler:
                async def fetch(self, request):
                    state = decode(await request.read())
                    self.engine.engine.import_request("r", [1], None, state)
        """, "KGCT016", relpath="serving/api_server.py")
        assert len(found) == 1 and "worker" in found[0].message

    def test_worker_wrapped_import_silent(self):
        assert lint("""
            class Handler:
                async def fetch(self, request):
                    state = decode(await request.read())
                    await self.engine.run_in_worker(
                        lambda e: e.import_request("r", [1], None, state))
        """, "KGCT016", relpath="serving/api_server.py") == []

    def test_streamed_chunk_scatter_outside_worker_fires(self):
        found = lint("""
            async def pull(engine, dec, data):
                for ck, cv in dec.feed(data):
                    engine.import_prefix_chunk("h", ck, cv)
        """, "KGCT016", relpath="serving/api_server.py")
        assert found and "import_prefix_chunk" in found[0].message

    def test_post_to_worker_cleanup_silent(self):
        assert lint("""
            def cleanup(self, handle):
                self.engine.post_to_worker(
                    lambda e: e.abort_prefix_import(handle))
        """, "KGCT016", relpath="serving/api_server.py") == []

    def test_kv_cache_rebind_fires(self):
        found = lint("""
            def f(engine, kv):
                engine.kv_cache = kv
        """, "KGCT016", relpath="serving/router.py")
        assert found and "kv_cache" in found[0].message

    def test_engine_modules_out_of_scope(self):
        """The engine package IS the seam's home; the rule polices only
        serving-side entry points."""
        assert lint("""
            def f(self, state):
                self.import_request("r", [1], None, state)
        """, "KGCT016", relpath="engine/engine.py") == []

    def test_async_engine_worker_loop_exempt(self):
        """The worker loop executes the seam by definition — it is the
        other side of run_in_worker, not a bypass."""
        assert lint("""
            def _worker(self):
                self.engine.import_request("r", [1], None, {})
        """, "KGCT016", relpath="serving/async_engine.py") == []


class TestDraftStateBoundary:  # KGCT017
    def test_direct_draft_kv_reach_fires(self):
        found = lint("""
            def step(self):
                kv = self.scheduler.spec_proposer.kv_cache
        """, "KGCT017", relpath="engine/engine.py")
        assert len(found) == 1 and "kv_cache" in found[0].message

    def test_alias_then_allocator_reach_fires(self):
        """A local alias of the proposer handle must not launder the
        reach: taint follows simple assignments."""
        found = lint("""
            def grow(sched):
                proposer = sched.spec_proposer
                pages = proposer.allocator.allocate(2)
        """, "KGCT017", relpath="engine/scheduler.py")
        assert len(found) == 1 and "allocator" in found[0].message

    def test_attr_assignment_through_handle_fires(self):
        found = lint("""
            def tune(sched):
                sched.spec_proposer.k = 8
        """, "KGCT017", relpath="engine/scheduler.py")
        assert len(found) == 1

    def test_draft_params_rebind_fires(self):
        found = lint("""
            def swap_weights(self, params):
                self.scheduler.spec_proposer.params = params
        """, "KGCT017", relpath="engine/engine.py")
        assert len(found) >= 1

    def test_proposer_seam_silent(self):
        """Installation + the seam methods (propose_batch/retain/k/
        compiled_variants) are the sanctioned surface."""
        assert lint("""
            def build(self, config, seqs):
                self.scheduler.spec_proposer = build_draft_runner(config)
                self.scheduler.spec_proposer.retain(ids)
                drafts = self.scheduler.spec_proposer.propose_batch(seqs, 4)
                k = self.scheduler.spec_proposer.k
                proposer = self.scheduler.spec_proposer
                if hasattr(proposer, "compiled_variants"):
                    n = proposer.compiled_variants()
        """, "KGCT017", relpath="engine/engine.py") == []

    def test_spec_package_is_the_implementation(self):
        """engine/spec/ OWNS the state — the rule polices reaches from
        outside, not the implementation itself."""
        assert lint("""
            def _grow(self, row):
                self.kv_cache = self.allocator.allocate(1)
                row.pages = self.spec_proposer.kv_cache
        """, "KGCT017", relpath="engine/spec/draft_model.py") == []

    def test_outside_engine_scope_silent(self):
        assert lint("""
            def f(e):
                kv = e.scheduler.spec_proposer.kv_cache
        """, "KGCT017", relpath="serving/api_server.py") == []


class TestWireIntegrity:  # KGCT018
    def test_unverified_commit_fires(self):
        found = lint("""
            async def fleet_import(self, handle):
                await self.engine.run_in_worker(
                    lambda e: e.commit_prefix_import(handle))
        """, "KGCT018", relpath="serving/api_server.py")
        assert len(found) == 1 and "checksum-verify" in found[0].message

    def test_unverified_import_request_fires(self):
        found = lint("""
            async def restore(self, rid, ids, params, state):
                await self.engine.run_in_worker(
                    lambda e: e.import_request(rid, ids, params, state))
        """, "KGCT018", relpath="serving/api_server.py")
        assert len(found) == 1

    def test_unverified_resume_import_fires(self):
        found = lint("""
            def resume(self, rid, ids, params, parked):
                return self.engine.generate(rid, ids, params,
                                            handoff=parked)
        """, "KGCT018", relpath="serving/api_server.py")
        assert len(found) == 1 and "generate" in found[0].message

    def test_verify_in_same_function_silent(self):
        assert lint("""
            def resume(self, rid, ids, params, parked):
                verify_import_state(parked)
                return self.engine.generate(rid, ids, params,
                                            handoff=parked)
        """, "KGCT018", relpath="serving/api_server.py") == []

    def test_verify_in_transitive_callee_silent(self):
        """The reaching path follows intra-module helpers: the pull
        helper's verifying decode covers the caller's commit."""
        assert lint("""
            async def _pull(self, url, rid):
                data = await fetch(url)
                state = decode_handoff(data, require_integrity=True)
                return state

            async def run(self, rid, ids, params, url):
                handoff = await self._pull(url, rid)
                return self.engine.generate(rid, ids, params,
                                            handoff=handoff)
        """, "KGCT018", relpath="serving/api_server.py") == []

    def test_decoder_construction_counts_as_verify(self):
        assert lint("""
            async def _pull_prefix(self, resp, handle):
                dec = PrefixStreamDecoder(require_integrity=True)
                async for chunk in resp:
                    dec.feed(chunk)
                await self.engine.run_in_worker(
                    lambda e: e.commit_prefix_import(handle))
        """, "KGCT018", relpath="serving/api_server.py") == []

    def test_handoff_none_generate_silent(self):
        """The plain serve path (no wire state) is not a commit."""
        assert lint("""
            def run(self, rid, ids, params):
                return self.engine.generate(rid, ids, params,
                                            handoff=None)
        """, "KGCT018", relpath="serving/api_server.py") == []

    def test_raw_frombuffer_fires(self):
        found = lint("""
            import numpy as np

            def decode(data):
                return np.frombuffer(data, dtype=np.uint8)
        """, "KGCT018", relpath="serving/api_server.py")
        assert len(found) == 1 and "frombuffer" in found[0].message

    def test_codec_and_worker_loop_exempt(self):
        assert lint("""
            import numpy as np

            def decode(data):
                return np.frombuffer(data, dtype=np.uint8)
        """, "KGCT018", relpath="serving/handoff.py") == []
        assert lint("""
            def _drain_inbox(self, e, rid, ids, params, state):
                e.import_request(rid, ids, params, state)
        """, "KGCT018", relpath="serving/async_engine.py") == []

    def test_outside_serving_silent(self):
        assert lint("""
            def commit(self, handle):
                self.commit_prefix_import(handle)
        """, "KGCT018", relpath="engine/engine.py") == []


class TestAwaitAtomicity:  # KGCT019
    def test_guard_await_claim_fires(self):
        found = lint("""
            class H:
                async def admit(self, rid, req):
                    if rid not in self._active:
                        ok = await self.check(req)
                        self._active[rid] = ok
        """, "KGCT019", relpath="serving/api_server.py")
        assert len(found) == 1 and "_active" in found[0].message

    def test_mutator_claim_after_await_fires(self):
        found = lint("""
            class H:
                async def track(self, rid):
                    if rid not in self._mid_stream:
                        await self._announce(rid)
                        self._mid_stream.add(rid)
        """, "KGCT019", relpath="serving/api_server.py")
        assert len(found) == 1 and "_mid_stream" in found[0].message

    def test_is_none_guard_with_await_in_claim_fires(self):
        # The double-create shape: both callers pass `is None`, both await
        # the constructor, the second overwrites (and leaks) the first.
        found = lint("""
            class H:
                async def session(self):
                    if self._http is None:
                        self._http = await make_session()
                    return self._http
        """, "KGCT019", relpath="serving/api_server.py")
        assert len(found) == 1

    def test_no_await_between_guard_and_claim_silent(self):
        # Check-then-act with nothing interleaved IS atomic on the loop —
        # the real _pull_prefix lazy-session shape.
        assert lint("""
            class H:
                async def session(self, req):
                    if self._http is None:
                        self._http = make_session()
                    await self._http.post(req)
        """, "KGCT019", relpath="serving/api_server.py") == []

    def test_while_recheck_guard_silent(self):
        # A while re-evaluates its condition after every await: the
        # condition-variable idiom, no stale-guard window.
        assert lint("""
            class H:
                async def wait_slot(self, rid):
                    while rid in self._active:
                        await asyncio.sleep(0)
                    self._active[rid] = True
        """, "KGCT019", relpath="serving/api_server.py") == []

    def test_sync_reservation_seam_silent(self):
        # The declared atomic-reservation seam: a sync def cannot suspend,
        # so check-and-claim cannot race itself on the loop.
        assert lint("""
            class E:
                def reserve_request_id(self, rid):
                    if rid in self._queues:
                        return False
                    self._queues[rid] = make_queue()
                    self._reserved.add(rid)
                    return True
        """, "KGCT019", relpath="serving/async_engine.py") == []

    def test_outside_serving_silent(self):
        assert lint("""
            class H:
                async def admit(self, rid, req):
                    if rid not in self._active:
                        ok = await self.check(req)
                        self._active[rid] = ok
        """, "KGCT019", relpath="engine/fake.py") == []


class TestThreadOwnership:  # KGCT020
    def test_iteration_through_alias_fires(self):
        found = lint("""
            class S:
                async def scrape(self):
                    sched = self.engine.engine.scheduler
                    return [r.request_id for r in sched.running]
        """, "KGCT020", relpath="serving/api_server.py")
        assert len(found) == 1 and "iterates" in found[0].message

    def test_method_call_on_owned_state_fires(self):
        found = lint("""
            class S:
                async def compact(self):
                    self.engine.engine.scheduler.preempt_lowest()
        """, "KGCT020", relpath="serving/api_server.py")
        assert len(found) == 1 and "calls a method" in found[0].message

    def test_subscript_fires(self):
        found = lint("""
            class S:
                async def peek(self):
                    eng = self.engine.engine
                    return eng.scheduler.waiting[0]
        """, "KGCT020", relpath="serving/api_server.py")
        assert len(found) == 1 and "subscripts" in found[0].message

    def test_rebind_fires(self):
        found = lint("""
            class S:
                async def reset(self):
                    self.engine.engine.scheduler = None
        """, "KGCT020", relpath="serving/api_server.py")
        assert len(found) == 1 and "rebinds" in found[0].message

    def test_gil_atomic_snapshots_silent(self):
        # The /healthz queue-depth gauges: len()/truthiness/is-None read
        # one reference atomically and copy nothing mutable.
        assert lint("""
            class S:
                async def health(self):
                    sched = self.engine.engine.scheduler
                    depth = len(sched.waiting) + len(sched.running)
                    ok = bool(depth) if sched.swapped is None else True
                    if sched.waiting:
                        depth += 1
                    return depth
        """, "KGCT020", relpath="serving/api_server.py") == []

    def test_worker_op_seam_silent(self):
        assert lint("""
            class S:
                async def depth(self):
                    return await self.engine.run_in_worker(
                        lambda e: [r.request_id for r in e.scheduler.running])
        """, "KGCT020", relpath="serving/api_server.py") == []

    def test_sync_setup_silent(self):
        # __init__ runs before the worker thread exists.
        assert lint("""
            class S:
                def __init__(self, engine):
                    kv = engine.engine.kv_cache
                    self.pages = kv.num_pages()
        """, "KGCT020", relpath="serving/api_server.py") == []

    def test_async_engine_module_exempt(self):
        assert lint("""
            class A:
                async def drain(self):
                    self.engine.scheduler.abort_all()
        """, "KGCT020", relpath="serving/async_engine.py") == []

    def test_outside_serving_silent(self):
        assert lint("""
            class S:
                async def scrape(self):
                    return [r for r in self.engine.engine.scheduler.running]
        """, "KGCT020", relpath="engine/fake.py") == []


class TestLockDiscipline:  # KGCT021
    def test_await_under_lock_fires(self):
        found = lint("""
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                async def flush(self):
                    with self._lock:
                        await self._send()
        """, "KGCT021", relpath="serving/api_server.py")
        assert len(found) == 1 and "await while holding" in found[0].message

    def test_blocking_under_loop_contended_lock_fires(self):
        # The indirect stall: the worker sleeps under a lock an async
        # handler also acquires — the handler blocks the WHOLE loop in
        # acquire() for the sleep's duration.
        found = lint("""
            import threading, time

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                async def touch(self):
                    with self._lock:
                        self.n += 1

                def worker_side(self):
                    with self._lock:
                        time.sleep(1.0)
        """, "KGCT021", relpath="serving/api_server.py")
        assert len(found) == 1 and "time.sleep" in found[0].message

    def test_cross_boundary_lock_fires_at_both_sites(self):
        found = lint("""
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._thread = threading.Thread(target=self._run)

                def _run(self):
                    with self._lock:
                        self.q.append(1)

                async def submit(self, x):
                    with self._lock:
                        self.q.append(x)
        """, "KGCT021", relpath="serving/api_server.py")
        assert len(found) == 2
        assert all("both sides" in f.message for f in found)

    def test_worker_only_lock_over_blocking_send_silent(self):
        # The directive leader's shape: the lock serializes the worker and
        # heartbeat threads; no event-loop code ever contends for it, so
        # blocking sends under it stall nobody's loop.
        assert lint("""
            import threading, time

            class L:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._thread = threading.Thread(target=self._run)

                def _run(self):
                    with self._lock:
                        time.sleep(0.1)

                def heartbeat(self):
                    with self._lock:
                        time.sleep(0.1)
        """, "KGCT021", relpath="serving/multihost.py") == []

    def test_handshake_module_exempt_from_cross_boundary(self):
        # AsyncLLMEngine._cv IS the sanctioned loop/worker handshake.
        assert lint("""
            import threading

            class A:
                def __init__(self):
                    self._cv = threading.Condition()
                    self._thread = threading.Thread(target=self._worker)

                def _worker(self):
                    with self._cv:
                        self._cv.wait()

                async def generate(self, item):
                    with self._cv:
                        self._inbox.append(item)
                        self._cv.notify()
        """, "KGCT021", relpath="serving/async_engine.py") == []

    def test_condition_wait_not_blocking_set(self):
        # wait/wait_for RELEASE the lock while waiting — the handshake
        # idiom is not a blocking call under the lock.
        found = lint("""
            import threading

            class A:
                def __init__(self):
                    self._cv = threading.Condition()

                async def poke(self):
                    with self._cv:
                        self._cv.notify()

                def worker(self):
                    with self._cv:
                        self._cv.wait_for(lambda: self.ready)
        """, "KGCT021", relpath="serving/fake.py")
        assert found == []
