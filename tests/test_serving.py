"""Continuous-batching serving engine tests.

The load-bearing pin is GREEDY PARITY: any request pushed through the
slot engine — whatever slot it lands in, however its prompt was
chunked, whoever shared its decode iterations — must produce
token-for-token the same output as a single-request ``generate`` call.
That one property proves admission, chunked prefill, per-slot
positions/masks, the wpos parking contract, EOS retirement, and slot
reuse all at once, so the e2e tests below assert it under staggered
mixed-length concurrent load rather than in isolation.
"""

from __future__ import annotations

import ast
import json
import threading
import time
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import (
    TransformerConfig,
    decode_weights,
    generate,
    init_params,
)
from tony_tpu.models import decode as decode_lib
from tony_tpu.observability.metrics import MetricsRegistry
from tony_tpu.serving import ServingEngine, ServingQueueFull
from tony_tpu.serving import engine as engine_lib
from tony_tpu.serving.scheduler import _chunk_plan


def _tiny_setup(n_experts: int = 0):
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16,
        d_ff=64, max_seq=96, dtype="float32", remat=False,
        n_experts=n_experts, expert_top_k=2 if n_experts else 0,
    )
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


class TestChunkPlan:
    def test_short_prompt_single_padded_chunk(self):
        assert _chunk_plan(3, 8) == [(0, 3)]

    def test_exact_multiple(self):
        assert _chunk_plan(16, 8) == [(0, 8), (8, 8)]

    def test_remainder_overlapped_final_chunk(self):
        # 20 = 2 full chunks + an overlapped final chunk at 12: every
        # chunk fully valid, overlap rewrites identical K/V.
        assert _chunk_plan(20, 8) == [(0, 8), (8, 8), (12, 8)]


class TestSubmitValidation:
    def test_rejects_bad_requests(self):
        cfg, params = _tiny_setup()
        eng = ServingEngine(params, cfg, slots=2, max_len=32)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit([], 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1, 2], 0)
        with pytest.raises(ValueError, match="KV capacity"):
            eng.submit(list(range(30)), 8)  # 30 + 8 > 32
        with pytest.raises(ValueError, match="temperature"):
            eng.submit([1, 2], 4, temperature=-1.0)

    def test_queue_backpressure_sheds(self):
        cfg, params = _tiny_setup()
        eng = ServingEngine(params, cfg, slots=1, max_queue=2)
        for _ in range(2):
            eng.submit([1, 2], 2)
        with pytest.raises(ServingQueueFull):
            eng.submit([1, 2], 2)

    def test_rejects_oversized_max_len(self):
        cfg, params = _tiny_setup()
        with pytest.raises(ValueError, match="max_seq"):
            ServingEngine(params, cfg, max_len=cfg.max_seq + 1)


class TestEngineParity:
    """The acceptance e2e: >= 8 staggered mixed-length requests through
    admission -> chunked prefill -> EOS retirement -> slot reuse, each
    matching its single-request greedy ``generate`` reference."""

    @pytest.mark.parametrize("window,prefill_batch", [(1, 1), (4, 3)])
    def test_staggered_mixed_length_requests_match_references(
        self, window, prefill_batch
    ):
        cfg, params = _tiny_setup()
        rng = np.random.default_rng(7)
        lens = (3, 7, 12, 20, 5, 11, 17, 9, 6, 14)
        budgets = (6, 8, 9, 4, 12, 3, 8, 6, 10, 5)
        prompts = [rng.integers(0, 64, n).astype(np.int32) for n in lens]
        # Half the requests get a real EOS mid-stream, derived from
        # their plain greedy continuation, so retirement-before-budget
        # is actually exercised; the rest run to their token budget.
        eos_ids: list[int | None] = []
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            if i % 2 == 0 and n >= 4:
                plain = np.asarray(
                    generate(params, jnp.asarray(p)[None], cfg, n)
                )[0]
                eos_ids.append(int(plain[n // 2]))
            else:
                eos_ids.append(None)

        registry = MetricsRegistry()
        eng = ServingEngine(
            params, cfg, slots=3, prefill_chunk=5, decode_window=window,
            prefill_batch=prefill_batch, registry=registry,
        )
        assert eng.slots < len(prompts)  # slot reuse is forced
        with eng:  # engine loop thread runs; submissions are staggered
            reqs = []
            for i, (p, n, e) in enumerate(zip(prompts, budgets, eos_ids)):
                reqs.append(eng.submit(p, n, eos_id=e))
                if i % 3 == 2:
                    time.sleep(0.05)  # arrivals overlap in-flight decode
            results = [r.result(timeout=120) for r in reqs]

        for p, n, e, res in zip(prompts, budgets, eos_ids, results):
            if e is None:
                want = np.asarray(
                    generate(params, jnp.asarray(p)[None], cfg, n)
                )[0]
                assert res["length"] == n
            else:
                ref = generate(params, jnp.asarray(p)[None], cfg, n,
                               eos_id=e)
                want_len = int(np.asarray(ref.lengths)[0])
                want = np.asarray(ref.tokens)[0][:want_len]
                assert res["length"] == want_len
            np.testing.assert_array_equal(np.asarray(res["tokens"]), want)

        # Every slot was reused and everything retired.
        stats = eng.stats()
        assert stats["retired"] == len(prompts)
        assert stats["active_slots"] == 0 and stats["queue_depth"] == 0

        # Serving telemetry flowed through the registry.
        snap = registry.snapshot()
        assert snap["counters"]["tony_serving_requests_total"] == len(
            prompts
        )
        assert snap["counters"]["tony_serving_retired_total"] == len(
            prompts
        )
        assert snap["counters"]["tony_serving_generated_tokens_total"] > 0
        assert snap["histograms"]["tony_serving_ttft_ms"]["count"] == len(
            prompts
        )
        assert snap["histograms"]["tony_serving_inter_token_ms"][
            "count"
        ] > 0
        assert "tony_serving_queue_depth" in snap["gauges"]
        assert "tony_serving_active_slots" in snap["gauges"]
        assert "tony_serving_tokens_per_sec" in snap["gauges"]

    def test_padded_batch_parked_reuse_and_full_row(self):
        """The cache's edges in one run: a prefill batch padded with
        duplicates of its row 0 (fewer pending prompts than
        ``prefill_batch``), a slot taken again right after a retirement
        while its decode lane is parked at ``Tmax - 1``, and a prompt of
        ``Tmax - 1 - C`` tokens whose stream then fills its row to the
        end. Tokens match the single-request reference and every
        prompt's exported K/V rows match a plain whole-prompt forward."""
        cfg, params = _tiny_setup()
        t_max, chunk = 32, 4
        rng = np.random.default_rng(11)
        lens = (t_max - 1 - chunk, 5, 9, 6)
        budgets = (5, 3, 6, 4)
        prompts = [rng.integers(0, 64, n).astype(np.int32) for n in lens]
        eng = ServingEngine(params, cfg, slots=2, max_len=t_max,
                            prefill_chunk=chunk, prefill_batch=3)
        reqs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        rows: dict[int, tuple] = {}
        for _ in range(500):
            if all(r.done() for r in reqs):
                break
            eng.step()
            for i, r in enumerate(reqs):
                if (i not in rows and r.t_first_token is not None
                        and r in eng._slot_req):
                    slot = eng._slot_req.index(r)
                    rows[i] = tuple(
                        np.asarray(engine_lib.cache_export_rows(c, slot,
                                                                lens[i]))
                        for c in (eng._k, eng._v)
                    )
        stats = eng.stats()
        assert stats["prefill_rows_padded"] > 0
        assert stats["retired"] == len(prompts) > eng.slots
        fused = decode_weights(params, cfg)
        for i, (p, n, r) in enumerate(zip(prompts, budgets, reqs)):
            want = np.asarray(
                generate(params, jnp.asarray(p)[None], cfg, n)
            )[0]
            np.testing.assert_array_equal(
                np.asarray(r.result(1)["tokens"]), want
            )
            _, cache = decode_lib.advance(
                fused, decode_lib.init_cache(cfg, 1, len(p)),
                jnp.asarray(p)[None], cfg, prefill=True,
            )
            for got, ref in zip(rows[i], (cache["k"], cache["v"])):
                np.testing.assert_allclose(
                    got, np.asarray(ref)[:, 0], rtol=1e-5, atol=1e-5
                )

    def test_moe_trunk_parity(self):
        cfg, params = _tiny_setup(n_experts=2)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 64, n).astype(np.int32)
                   for n in (4, 9, 13)]
        eng = ServingEngine(params, cfg, slots=2, prefill_chunk=4)
        reqs = [eng.submit(p, 5) for p in prompts]
        for _ in range(500):
            if all(r.done() for r in reqs):
                break
            eng.step()
        for p, r in zip(prompts, reqs):
            want = np.asarray(
                generate(params, jnp.asarray(p)[None], cfg, 5)
            )[0]
            np.testing.assert_array_equal(
                np.asarray(r.result(1)["tokens"]), want
            )

    def test_temperature_request_runs_and_differs_from_greedy(self):
        cfg, params = _tiny_setup()
        prompt = np.arange(8, dtype=np.int32)
        eng = ServingEngine(params, cfg, slots=2, seed=5)
        hot = eng.submit(prompt, 16, temperature=1.5)
        cold = eng.submit(prompt, 16)
        for _ in range(500):
            if hot.done() and cold.done():
                break
            eng.step()
        greedy = np.asarray(
            generate(params, jnp.asarray(prompt)[None], cfg, 16)
        )[0]
        np.testing.assert_array_equal(
            np.asarray(cold.result(1)["tokens"]), greedy
        )
        # Sampling at temperature 1.5 over 16 draws flipping no token
        # vs greedy would be astronomically unlikely.
        assert not np.array_equal(
            np.asarray(hot.result(1)["tokens"]), greedy
        )

    def test_compile_instrumentation_counts_engine_executables(self):
        from tony_tpu.observability.metrics import default_registry

        cfg, params = _tiny_setup()
        reg = default_registry()

        def totals():
            snap = reg.snapshot()["counters"]
            return (snap.get("tony_compile_cache_hits_total", 0)
                    + snap.get("tony_compile_cache_misses_total", 0))

        eng = ServingEngine(params, cfg, slots=2, prefill_chunk=4)
        before = totals()
        r = eng.submit(np.arange(6, dtype=np.int32), 3)
        for _ in range(200):
            if r.done():
                break
            eng.step()
        r.result(1)
        # Exactly two instrumented first-compiles: the prefill batch and
        # the decode window.
        assert totals() == before + 2


class TestCachePoliciesOverOneLayer:
    """``advance``, ``decode_window`` and ``prefill_chunks`` run ONE layer
    (``models.decode.serve_layer``) and differ by cache policy alone, so
    the same tokens give the same LOGITS whichever of them reads them
    (the token parity above passes on any argmax that survives)."""

    @pytest.mark.parametrize("chunks", [1, 4])
    @pytest.mark.parametrize("n_experts", [0, 2])
    def test_prefill_chunks_logits_match_advance(self, n_experts, chunks):
        cfg, params = _tiny_setup(n_experts=n_experts)
        fused = decode_weights(params, cfg)
        c, t_max = 6, 40
        n = c * chunks
        prompts = jnp.asarray(np.random.default_rng(5).integers(
            0, cfg.vocab_size, (2, n)), jnp.int32)
        want, _ = decode_lib.advance(
            fused, decode_lib.init_cache(cfg, 2, n), prompts, cfg,
            prefill=True)
        k, v = engine_lib.init_slot_cache(cfg, 3, t_max, prefill_chunk=c)
        slots = jnp.asarray([2, 0], jnp.int32)
        for i in range(chunks):
            k, v, _, got, _ = engine_lib.prefill_chunks(
                fused, k, v, prompts[:, i * c:(i + 1) * c], slots,
                jnp.full((2,), i * c, jnp.int32), jnp.full((2,), c, jnp.int32),
                jnp.zeros((2,), jnp.float32), jax.random.key(0),
                jnp.int32(0), cfg=cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_decode_window_tokens_match_advance_beside_parked_lane(self):
        """GQA, one live slot and one parked lane: eight single-token
        ``advance`` steps against eight ``decode_window(steps=1)``
        dispatches from the same prefilled rows."""
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=96, dtype="float32", remat=False, n_kv_heads=2,
        )
        fused = decode_weights(init_params(jax.random.key(1), cfg), cfg)
        t_max, n, live = 32, 7, 1
        prompt = jnp.asarray(np.random.default_rng(9).integers(
            0, cfg.vocab_size, (1, n)), jnp.int32)
        logits, cache = decode_lib.advance(
            fused, decode_lib.init_cache(cfg, 1, t_max), prompt, cfg,
            prefill=True)
        k, v = engine_lib.init_slot_cache(cfg, 2, t_max)
        k = engine_lib.cache_inject_rows(k, live, cache["k"][:, 0, :n])
        v = engine_lib.cache_inject_rows(v, live, cache["v"][:, 0, :n])
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pos = np.array([0, n], np.int32)
        wpos = np.array([t_max - 1, n], np.int32)   # lane 0 is parked
        for step in range(8):
            logits, cache = decode_lib.advance(fused, cache, tok[:, None], cfg)
            k, v, got, _ = engine_lib.decode_window(
                fused, k, v, jnp.asarray(pos), jnp.asarray(wpos),
                jnp.asarray([0, int(tok[0])], jnp.int32),
                jnp.zeros((2,), jnp.float32), jax.random.key(0),
                jnp.int32(step), cfg=cfg, steps=1)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            assert int(got[live, 0]) == int(tok[0]), step
            pos[live] += 1
            wpos[live] += 1


    def test_decode_window_kernel_reads_nothing_for_a_parked_lane(
            self, monkeypatch):
        """``decode_window`` through the decode KERNEL (interpret mode,
        key blocks of 8 positions) beside the plain path: a live slot
        that crosses a block edge, a free lane with a last tenant's
        stale position and a lane in prefill (pos 0), both parked, whose
        slots hold NaN in every row. The live slot's tokens are the plain
        path's; what the parked lanes wrote into their parking row in
        the SECOND layer is finite, so the first layer's attention read
        none of their rows."""
        import functools

        from tony_tpu.ops import cache_decode_attention

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=96, dtype="float32", remat=False, n_kv_heads=2,
        )
        fused = decode_weights(init_params(jax.random.key(1), cfg), cfg)
        t_max, n, live = 32, 6, 1
        prompt = jnp.asarray(np.random.default_rng(9).integers(
            0, cfg.vocab_size, (1, n)), jnp.int32)
        logits, cache = decode_lib.advance(
            fused, decode_lib.init_cache(cfg, 1, t_max), prompt, cfg,
            prefill=True)
        first = int(jnp.argmax(logits, axis=-1)[0])

        def run(kernel: bool):
            if kernel:
                monkeypatch.setattr(
                    engine_lib, "cache_decode_attention", functools.partial(
                        cache_decode_attention, mode="interpret",
                        block_rows=16))
            engine_lib.decode_window.clear_cache()
            k, v = (jnp.full_like(c, jnp.nan)
                    for c in engine_lib.init_slot_cache(cfg, 3, t_max))
            k = engine_lib.cache_inject_rows(
                k.at[:, live].set(0), live, cache["k"][:, 0, :n])
            v = engine_lib.cache_inject_rows(
                v.at[:, live].set(0), live, cache["v"][:, 0, :n])
            pos = np.array([20, n, 0], np.int32)
            wpos = np.array([t_max - 1, n, t_max - 1], np.int32)
            tok, toks = first, []
            for step in range(5):
                k, v, got, _ = engine_lib.decode_window(
                    fused, k, v, jnp.asarray(pos), jnp.asarray(wpos),
                    jnp.asarray([3, tok, 5], jnp.int32),
                    jnp.zeros((3,), jnp.float32), jax.random.key(0),
                    jnp.int32(step), cfg=cfg, steps=1)
                tok = int(got[live, 0])
                toks.append(tok)
                pos[live] += 1
                wpos[live] += 1
            return toks, np.asarray(k[1, [0, 2], t_max - 1])

        try:
            want, _ = run(kernel=False)
            got, parked_rows = run(kernel=True)
        finally:
            engine_lib.decode_window.clear_cache()
        assert got == want
        assert np.isfinite(parked_rows).all()


def _module_ast(relpath: str):
    path = Path(__file__).resolve().parents[1] / "tony_tpu" / relpath
    return ast.parse(path.read_text())


class TestOneInferenceLayer:
    """Static: the decoder layer of inference has ONE definition, in
    ``models/decode.py``, and the cache one storage decision."""

    LAYER_WEIGHTS = {"qkv", "gate_up", "w_down", "ln1", "ln2", "unembed",
                     "final_norm"}

    def test_engine_holds_no_layer_arithmetic(self):
        tree = _module_ast("serving/engine.py")
        subscripts = {
            node.slice.value for node in ast.walk(tree)
            if isinstance(node, ast.Subscript)
            and isinstance(node.slice, ast.Constant)
        }
        assert not subscripts & self.LAYER_WEIGHTS
        called = {
            getattr(node.func, "id", getattr(node.func, "attr", None))
            for node in ast.walk(tree) if isinstance(node, ast.Call)
        }
        assert "rms_norm" not in called
        # of models/ it takes the configuration and the layer's public
        # functions, nothing private
        for node in ast.walk(tree):
            if (isinstance(node, ast.ImportFrom)
                    and (node.module or "").startswith("tony_tpu.models")):
                names = [a.name for a in node.names]
                assert not [n for n in names if n.startswith("_")], names

    def test_decode_defines_the_layer_and_the_head_once(self):
        tree = _module_ast("models/decode.py")
        defined = [n.name for n in ast.walk(tree)
                   if isinstance(n, ast.FunctionDef)]
        assert "_layer_decode" not in defined
        for name in ("serve_layer", "run_layers", "lm_head"):
            assert defined.count(name) == 1
        # the head's weight is read where the logits are made (lm_head)
        # and where the training layout is re-packed (decode_weights),
        # and by no other function of the module
        readers = {
            fn.name for fn in ast.walk(tree)
            if isinstance(fn, ast.FunctionDef)
            for node in ast.walk(fn)
            if isinstance(node, ast.Subscript)
            and isinstance(node.slice, ast.Constant)
            and node.slice.value == "unembed"
        }
        assert readers == {"lm_head", "decode_weights"}

    def test_no_module_names_a_quantized_cache(self):
        root = Path(__file__).resolve().parents[1] / "tony_tpu"
        files = [root / "serving/engine.py", root / "models/decode.py",
                 *sorted((root / "conf").iterdir())]
        named = [
            str(f) for f in files if f.is_file()
            and any(word in f.read_text()
                    for word in ("int8", "QuantizedKV"))
        ]
        assert not named


def _cache_writes(jaxpr):
    """(primitive, operand shape, update shape) of every
    ``dynamic_update_slice`` / ``scatter*`` in ``jaxpr``, the bodies of
    scan / while / cond / pjit included."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dynamic_update_slice":
            yield name, eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
        elif name.startswith("scatter"):
            yield name, eqn.invars[0].aval.shape, eqn.invars[2].aval.shape
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _cache_writes(sub)


class TestCacheWritesAreRows:
    """Structure, not speed: no write into a cache buffer may carry an
    update as large as one layer's slab — the programs append rows to
    the stacked, donated buffer where they lie. (The slab path this
    replaced copied [S, Tmax, Hkv, Dh] out of the buffer and back, per
    buffer, per layer, per dispatch: over half the device time of both
    serving cells.)"""

    @pytest.mark.parametrize("program", ["decode_window", "prefill_chunks"])
    def test_no_write_is_slab_sized(self, program):
        cfg, params = _tiny_setup()
        fused = decode_weights(params, cfg)
        slots, t_max, p, c = 3, 24, 2, 4
        k, v = engine_lib.init_slot_cache(cfg, slots, t_max)
        key = jax.random.key(0)
        if program == "decode_window":
            lane = jnp.zeros((slots,), jnp.int32)
            jaxpr = jax.make_jaxpr(
                lambda *a: engine_lib.decode_window(*a, cfg=cfg, steps=2)
            )(fused, k, v, lane, lane, lane,
              jnp.zeros((slots,), jnp.float32), key, jnp.int32(0))
        else:
            row = jnp.zeros((p,), jnp.int32)
            jaxpr = jax.make_jaxpr(
                lambda *a: engine_lib.prefill_chunks(*a, cfg=cfg)
            )(fused, k, v, jnp.zeros((p, c), jnp.int32), row, row, row + c,
              jnp.zeros((p,), jnp.float32), key, jnp.int32(0))
        rows = max(slots, p * c) * cfg.kv_heads * cfg.head_dim
        slab = slots * t_max * cfg.kv_heads
        writes = [
            w for w in _cache_writes(jaxpr.jaxpr)
            if int(np.prod(w[1])) >= slab  # a layer's slab or the buffer
        ]
        assert writes, "the program no longer writes its cache"
        too_big = [w for w in writes if int(np.prod(w[2])) > rows]
        assert not too_big, f"slab-sized cache writes: {too_big}"


class TestServingHTTP:
    def test_generate_healthz_shutdown(self):
        from tony_tpu.serving.http import ServingServer

        cfg, params = _tiny_setup()
        eng = ServingEngine(params, cfg, slots=2).start()
        server = ServingServer(eng, port=0)
        port = server.start()
        try:
            prompt = list(range(1, 7))
            body = json.dumps({
                "prompt": prompt, "max_new_tokens": 5,
            }).encode()
            with urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/generate", data=body,
                headers={"Content-Type": "application/json"},
            ), timeout=120) as resp:
                out = json.loads(resp.read())
            want = np.asarray(generate(
                params, jnp.asarray(prompt, jnp.int32)[None], cfg, 5
            ))[0]
            np.testing.assert_array_equal(np.asarray(out["tokens"]), want)
            assert out["length"] == 5 and out["wall_ms"] >= 0

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as resp:
                health = json.loads(resp.read())
            assert health["slots"] == 2 and health["retired"] == 1

            # Malformed body -> 400, not a wedged connection.
            bad = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate", data=b"{}",
            )
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(bad, timeout=10)
            assert err.value.code == 400

            with urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/shutdown", data=b"",
            ), timeout=10) as resp:
                assert json.loads(resp.read())["ok"] is True
            assert server.wait_shutdown(timeout=10)
        finally:
            server.stop()
            eng.close()

    def test_close_fails_pending_requests(self):
        cfg, params = _tiny_setup()
        eng = ServingEngine(params, cfg, slots=1)
        req = eng.submit([1, 2, 3], 4)  # never stepped
        eng.close()
        with pytest.raises(RuntimeError, match="shut down"):
            req.result(timeout=1)


class TestProxyCounters:
    """Satellite: tony.proxy.connect-timeout + byte counters."""

    def test_tunnel_counts_bytes_by_direction(self):
        import socket
        import socketserver

        class Echo(socketserver.ThreadingTCPServer):
            allow_reuse_address = True

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                data = self.rfile.read(5)
                self.wfile.write(data.upper())

        upstream = Echo(("127.0.0.1", 0), Handler)
        threading.Thread(target=upstream.serve_forever,
                         daemon=True).start()
        registry = MetricsRegistry()
        from tony_tpu.proxy import ProxyServer

        proxy = ProxyServer(
            "127.0.0.1", upstream.server_address[1], 0,
            connect_timeout_s=2.0, registry=registry,
        )
        port = proxy.start()
        try:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=10) as sock:
                sock.sendall(b"hello")
                assert sock.recv(5) == b"HELLO"
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                counters = registry.snapshot()["counters"]
                up = counters.get(
                    'tony_proxy_bytes_total{direction="up"}', 0)
                down = counters.get(
                    'tony_proxy_bytes_total{direction="down"}', 0)
                if up >= 5 and down >= 5:
                    break
                time.sleep(0.05)
            assert up == 5 and down == 5
        finally:
            proxy.stop()
            upstream.shutdown()
            upstream.server_close()

    def test_connect_timeout_is_configurable(self):
        from tony_tpu.proxy import ProxyServer

        proxy = ProxyServer("127.0.0.1", 1, 0, connect_deadline_s=0.0,
                            connect_timeout_s=0.05,
                            registry=MetricsRegistry())
        t0 = time.monotonic()
        assert proxy._connect_upstream() is None
        assert time.monotonic() - t0 < 5.0  # old hardcoded floor

    def test_conf_key_registered_and_validated(self):
        from tony_tpu.analysis.config_check import check_config
        from tony_tpu.conf import keys
        from tony_tpu.conf.configuration import TonyConfiguration

        assert keys.DEFAULTS[keys.K_PROXY_CONNECT_TIMEOUT_MS] == 5000
        conf = TonyConfiguration()
        conf.set(keys.K_PROXY_CONNECT_TIMEOUT_MS, 0)
        assert any(
            f.rule_id == "TONY-C002" and "connect-timeout" in f.message
            for f in check_config(conf)
        )

    def test_serving_keys_validated(self):
        from tony_tpu.analysis.config_check import check_config
        from tony_tpu.conf import keys
        from tony_tpu.conf.configuration import TonyConfiguration

        for key in (keys.K_SERVING_SLOTS, keys.K_SERVING_PREFILL_CHUNK,
                    keys.K_SERVING_DECODE_WINDOW,
                    keys.K_SERVING_MAX_QUEUE):
            conf = TonyConfiguration()
            conf.set(key, 0)
            assert any(f.rule_id == "TONY-C002" for f in check_config(conf)), key
        conf = TonyConfiguration()
        conf.set(keys.K_SERVING_PORT, 0)  # 0 = ephemeral is legal
        assert not [f for f in check_config(conf) if f.rule_id == "TONY-C002"]


class TestBenchServingGate:
    """The bench_serving sub-metrics flatten into gated names and the
    seeded cpu baseline catches a serving-throughput collapse."""

    _LINE = {
        "metric": "x",
        "extras": {"device": "cpu", "serving": {
            "wall_tokens_per_sec": 1341, "sustained_tokens_per_sec": 1577,
            "generate_wall_tokens_per_sec": 4530,
            "generate_wall_speedup": 0.35,
            "single_shot_wall_tokens_per_sec": 942,
            "single_shot_speedup": 1.67,
            "inter_token_p50_ms": 4.5, "inter_token_p95_ms": 13.6,
            "ttft_p50_ms": 440.0, "ttft_p95_ms": 1791.0,
            "generated_tokens": 3000, "slots": 16, "n_requests": 128,
            "prefill_chunk": 32, "decode_window": 8, "out_mean": 32.0,
            "d_model": 128,
        }},
    }

    def _bench(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench", Path(__file__).resolve().parent.parent / "bench.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_seeded_cpu_gate_passes_and_catches_collapse(self):
        bench = self._bench()
        current = bench.collect_submetrics(self._LINE)
        assert current["serving.single_shot_speedup"] == 1.67
        assert "serving.slots" not in current  # shape params ungated
        # The cpu table also gates other workload families (scheduler);
        # this synthetic line is serving-only, so gate that subset — a
        # REAL bench line carries every family and gates them all.
        baseline = {
            k: v for k, v in bench.load_baselines().get("cpu", {}).items()
            if k.startswith("serving.")
        }
        assert baseline, "cpu serving baselines must be seeded"
        assert not bench.check_regressions(current, baseline)
        collapsed = dict(current)
        collapsed["serving.single_shot_speedup"] = 0.5
        collapsed["serving.sustained_tokens_per_sec"] = 300.0
        problems = bench.check_regressions(collapsed, baseline)
        assert any("single_shot_speedup" in p for p in problems)
        assert any("sustained_tokens_per_sec" in p for p in problems)


@pytest.mark.slow
class TestMiniClusterServing:
    """The full wire: a `serving` task type submitted to the mini
    cluster runs examples/lm_serve.py (checkpointless smoke weights),
    the test tunnels to it through ProxyServer exactly as a gateway
    would, drives generate requests end to end, and the job SUCCEEDs
    after /shutdown — with the tunnel's byte counters ticking."""

    def test_serving_task_through_proxy(self, tmp_path):
        import sys

        from tony_tpu.conf import keys
        from tony_tpu.coordinator.session import SessionStatus
        from tony_tpu.mini import MiniTonyCluster
        from tony_tpu.proxy import ProxyServer

        repo = Path(__file__).resolve().parent.parent
        addr_file = tmp_path / "serving.addr"
        with MiniTonyCluster(tmp_path / "cluster") as cluster:
            conf = cluster.base_conf()
            conf.set(keys.K_FRAMEWORK, "jax")
            conf.set(keys.K_EXECUTES,
                     str(repo / "examples" / "lm_serve.py"))
            conf.set(keys.K_PYTHON_BINARY, sys.executable)
            conf.set(keys.instances_key("worker"), 0)
            conf.set(keys.instances_key("ps"), 0)
            conf.set(keys.instances_key("serving"), 1)
            conf.set(keys.K_CHIEF_NAME, "serving")
            conf.set(keys.K_SERVING_SLOTS, 2)
            conf.set(keys.K_SERVING_PREFILL_CHUNK, 8)
            conf.set(keys.K_SERVING_DECODE_WINDOW, 2)
            conf.set(keys.K_TASK_PARAMS,
                     f"--max-seq 96 --seed 0 --addr-file {addr_file}")
            job = cluster.start_job(conf)
            proxy = None
            try:
                deadline = time.monotonic() + 180
                while not addr_file.exists():
                    assert job.running(), "serving job died before binding"
                    assert time.monotonic() < deadline, "no addr published"
                    time.sleep(0.25)
                host, _, port = addr_file.read_text().strip().rpartition(
                    ":")
                registry = MetricsRegistry()
                proxy = ProxyServer(host, int(port), 0,
                                    connect_timeout_s=conf.get_int(
                                        keys.K_PROXY_CONNECT_TIMEOUT_MS,
                                        5000) / 1000.0,
                                    registry=registry)
                local = proxy.start()
                base = f"http://127.0.0.1:{local}"

                prompt = [1, 5, 9, 2]
                body = json.dumps(
                    {"prompt": prompt, "max_new_tokens": 8}).encode()
                with urllib.request.urlopen(urllib.request.Request(
                    f"{base}/generate", data=body,
                ), timeout=180) as resp:
                    out = json.loads(resp.read())
                assert out["length"] == 8

                # Reference: the fixture serves fresh weights from
                # seed 0 with lm_train's default model flags — rebuild
                # the identical config/params here and pin parity
                # through the whole proxy -> engine wire.
                import argparse

                sys.path.insert(0, str(repo / "examples"))
                try:
                    import lm_train
                finally:
                    sys.path.pop(0)
                p = argparse.ArgumentParser()
                lm_train.add_model_args(p)
                cfg = lm_train.model_config_from_args(
                    p.parse_args([]), max_seq=96
                )
                params = init_params(jax.random.key(0), cfg)
                want = np.asarray(generate(
                    params, jnp.asarray(prompt, jnp.int32)[None], cfg, 8
                ))[0]
                np.testing.assert_array_equal(
                    np.asarray(out["tokens"]), want
                )

                with urllib.request.urlopen(f"{base}/healthz",
                                            timeout=30) as resp:
                    health = json.loads(resp.read())
                assert health["slots"] == 2 and health["retired"] >= 1

                counters = registry.snapshot()["counters"]
                assert counters['tony_proxy_bytes_total{direction="up"}'] > 0
                assert counters[
                    'tony_proxy_bytes_total{direction="down"}'] > 0

                with urllib.request.urlopen(urllib.request.Request(
                    f"{base}/shutdown", data=b"",
                ), timeout=30):
                    pass
                status = job.wait(timeout_s=120)
                assert status is SessionStatus.SUCCEEDED
            finally:
                if proxy is not None:
                    proxy.stop()


class TestDrain:
    def test_drain_completes_inflight_then_blocks_admission(self):
        cfg, params = _tiny_setup()
        eng = ServingEngine(params, cfg, slots=2)
        with eng:
            reqs = [eng.submit(np.arange(1, 6, dtype=np.int32), 6)
                    for _ in range(4)]
            assert eng.drain(timeout=60.0)
            for r in reqs:
                assert r.done() and r.error is None
                assert r.result(1)["length"] == 6
            with pytest.raises(RuntimeError, match="draining"):
                eng.submit([1, 2], 2)


class TestServingFleetSatellites:
    """PR-18 serving-side satellites: 429 + Retry-After shed signal,
    fleet-facing /healthz fields, gauge zeroing on drain/close, model
    multiplexing parity + LRU residency, and prefill/decode
    disaggregation parity over the HTTP wire format."""

    def test_http_429_retry_after_and_healthz_fleet_fields(self):
        from tony_tpu.serving.http import ServingServer

        cfg, params = _tiny_setup()
        # Engine deliberately NOT started: the queue can't drain, so
        # filling it is deterministic.
        eng = ServingEngine(params, cfg, slots=1, max_queue=1)
        eng.submit([1, 2, 3], 4)  # queue now at max_queue
        server = ServingServer(eng, port=0,
                               extra_health={"role": "prefill"})
        port = server.start()
        try:
            body = json.dumps({"prompt": [1, 2], "max_new_tokens": 2})
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/generate",
                    data=body.encode(),
                ), timeout=10)
            # Shed is distinguishable from failure: 429 + Retry-After,
            # which the fleet router uses to retry another replica.
            assert err.value.code == 429
            assert err.value.headers["Retry-After"] == "1"

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as resp:
                health = json.loads(resp.read())
            # The fields the router/autoscaler read, plus the merged
            # extra_health role the fleet layer advertises.
            assert health["active_slots"] == 0
            assert health["queue_depth"] == 1
            assert health["draining"] is False
            assert health["models"] == ["default"]
            assert health["role"] == "prefill"
        finally:
            server.stop()
            eng.close()

    def test_gauges_zeroed_on_drain_and_close(self):
        registry = MetricsRegistry()
        cfg, params = _tiny_setup()
        eng = ServingEngine(params, cfg, slots=2, registry=registry)
        with eng:
            reqs = [eng.submit([1, 2, 3, 4], 5) for _ in range(3)]
            assert eng.drain(timeout=60.0)
            for r in reqs:
                assert r.result(1)["length"] == 5
            # A drained replica must publish zero load — stale gauges
            # would keep attracting router traffic and block the
            # autoscaler's scale-down forever.
            for name in ("tony_serving_queue_depth",
                         "tony_serving_active_slots",
                         "tony_serving_tokens_per_sec"):
                assert registry.gauge(name).value == 0

        # close() without a drain (requests still queued) zeroes too.
        reg2 = MetricsRegistry()
        eng2 = ServingEngine(params, cfg, slots=1, registry=reg2)
        eng2.submit([1, 2], 3)  # never started, never stepped
        eng2.close()
        for name in ("tony_serving_queue_depth",
                     "tony_serving_active_slots",
                     "tony_serving_tokens_per_sec"):
            assert reg2.gauge(name).value == 0

    def test_multiplexing_parity_and_lru_residency(self):
        cfg, params_a = _tiny_setup()
        params_b = init_params(jax.random.key(1), cfg)
        params_c = init_params(jax.random.key(2), cfg)
        loads = {"b": 0, "c": 0}

        def load_b():
            loads["b"] += 1
            return params_b

        def load_c():
            loads["c"] += 1
            return params_c

        prompt = np.arange(1, 8, dtype=np.int32)
        want = {
            name: np.asarray(generate(
                p, jnp.asarray(prompt)[None], cfg, 6
            ))[0]
            for name, p in (("default", params_a), ("b", params_b),
                            ("c", params_c))
        }

        # max_resident_models=2: "default" (ctor weights, no loader —
        # pinned) + one loader-backed model; serving the other must
        # evict its sibling and re-fuse it on the next swap.
        eng = ServingEngine(params_a, cfg, slots=2,
                            max_resident_models=2)
        eng.add_model("b", loader=load_b)
        eng.add_model("c", loader=load_c)
        with eng:
            assert eng.stats()["models"] == ["b", "c", "default"]
            for name in ("b", "c", "default", "b"):
                got = eng.submit(prompt, 6, model=name).result(
                    timeout=120)
                np.testing.assert_array_equal(
                    np.asarray(got["tokens"]), want[name],
                    err_msg=f"model {name!r} diverged from its "
                            f"single-request generate reference",
                )
            # Serving "c" evicted "b" (LRU past the residency bound),
            # so the second "b" request re-fused from its loader.
            assert loads["b"] == 2 and loads["c"] == 1
            assert len(eng._resident) <= 2

    def test_disaggregation_parity_over_http_wire(self):
        from tony_tpu.serving.http import (ServingServer, decode_kv,
                                           encode_kv)

        cfg, params = _tiny_setup()
        prompt = list(range(2, 11))
        total_new = 6
        want = np.asarray(generate(
            params, jnp.asarray(prompt, jnp.int32)[None], cfg, total_new
        ))[0]

        # encode/decode roundtrip is exact for float32 KV.
        rng = np.random.default_rng(3)
        kk = rng.standard_normal((2, 4, 2, 16)).astype(np.float32)
        vv = rng.standard_normal((2, 4, 2, 16)).astype(np.float32)
        rk, rv = decode_kv(encode_kv(kk, vv))
        np.testing.assert_array_equal(rk, kk)
        np.testing.assert_array_equal(rv, vv)

        pre_eng = ServingEngine(params, cfg, slots=2).start()
        dec_eng = ServingEngine(params, cfg, slots=2).start()
        pre_srv = ServingServer(pre_eng, port=0)
        dec_srv = ServingServer(dec_eng, port=0)
        pre_port = pre_srv.start()
        dec_port = dec_srv.start()

        def _post(port, path, obj):
            body = json.dumps(obj).encode()
            with urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}{path}", data=body,
                headers={"Content-Type": "application/json"},
            ), timeout=120) as resp:
                return json.loads(resp.read())

        try:
            # Prefill replica: chunked prefill + first token + exported
            # KV rows; the slot frees instead of decoding.
            pre = _post(pre_port, "/prefill", {
                "prompt": prompt, "max_new_tokens": total_new,
            })
            assert pre["last_token"] == int(want[0])
            assert pre["pos"] == len(prompt)
            assert pre["kv"]["shape"][1] == len(prompt)
            assert pre_eng.stats()["active_slots"] == 0

            # Decode replica: inject the shipped rows, decode the rest.
            dec = _post(dec_port, "/inject", {
                "kv": pre["kv"], "last_token": pre["last_token"],
                "pos": pre["pos"],
                "max_new_tokens": total_new - 1,
            })
            got = [pre["last_token"]] + list(dec["tokens"])
            np.testing.assert_array_equal(
                np.asarray(got), want,
                err_msg="disaggregated prefill->inject diverged from "
                        "single-engine generate",
            )
        finally:
            pre_srv.stop()
            dec_srv.stop()
            pre_eng.close()
            dec_eng.close()


class TestBenchFleetGate:
    """bench_serving_fleet sub-metrics flatten into gated names and the
    seeded cpu baselines catch a fleet-throughput collapse, a TTFT
    blow-up, and a dead (or slow) autoscaler."""

    _LINE = {
        "metric": "x",
        "extras": {"device": "cpu", "serving_fleet": {
            "fleet_wall_tokens_per_sec": 1459,
            "fleet_sustained_tokens_per_sec": 1912,
            "ttft_p50_ms": 167.8, "ttft_p95_ms": 318.9,
            "autoscale_reaction_ms": 15.5,
            "replicas_peak": 3, "scale_ups": 2, "requests_ok": 80,
            "requests_failed": 0, "generated_tokens": 1280,
            "slots": 4, "max_replicas": 3, "d_model": 128,
            # _safe stamps this whenever the jit sanitizer is armed
            # (always, under bench --check); baselined at absolute 0.
            "retraces_total": 0,
        }},
    }

    def test_seeded_cpu_gate_passes_and_catches_collapse(self):
        bench = TestBenchServingGate()._bench()
        current = bench.collect_submetrics(self._LINE)
        # Directionality: throughput gates higher-is-better, reaction
        # and TTFT lower-is-better, shape params ungated.
        assert bench.metric_direction(
            "serving_fleet.autoscale_reaction_ms") == "lower"
        assert bench.metric_direction(
            "serving_fleet.fleet_sustained_tokens_per_sec") == "higher"
        assert "serving_fleet.replicas_peak" not in current
        baseline = {
            k: v for k, v in bench.load_baselines().get("cpu", {}).items()
            if k.startswith("serving_fleet.")
        }
        assert baseline, "cpu serving_fleet baselines must be seeded"
        assert not bench.check_regressions(current, baseline)

        collapsed = dict(current)
        collapsed["serving_fleet.fleet_sustained_tokens_per_sec"] = 100.0
        # The no-scale-up sentinel (9e9) must fail the reaction gate.
        collapsed["serving_fleet.autoscale_reaction_ms"] = 9e9
        problems = bench.check_regressions(collapsed, baseline)
        assert any("fleet_sustained_tokens_per_sec" in p
                   for p in problems)
        assert any("autoscale_reaction_ms" in p for p in problems)
