"""The benchmark's own tests, on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They drive ``bench/run.py``'s whole run except its look for a chip, on a
tiny configuration and mixes that each test adds as new files beside the
real ones, so they also show that a cell, a mix, a metric, a reference
and a distribution are picked up by name without an edit.  The trace reduction is checked on a short
trace recorded on a TPU v5e and kept here.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(REPO / "src"))

import registry  # noqa: E402
import run  # noqa: E402

TINY_CELLS = ("tiny.tinychat", "tiny.tinylong")
TINY_LIMIT = 0.05      # widest logit gap allowed at the tiny size


# a new arrival process, added as a file: gamma gaps of mean 1 and the
# given coefficient of variation, at the quantiles of a fixed large draw
GAMMA_CV = """import numpy as np


def quantile(u, spec):
    k = spec["cv"] ** -2
    draw = np.random.default_rng(0).gamma(k, 1 / k, 200_000)
    return np.quantile(draw, u)
"""


def _tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout's data and bench code with a tiny configuration, its own
    reference, two tiny mixes (one with a new arrival process), their
    rate and limits, and one extra metric, all added as new files."""
    root = tmp / "root"
    for d in ("configs", "traffic", "checks", "metrics", "generators",
              "distributions", "reference"):
        shutil.copytree(BENCH / d, root / "bench" / d)
    (root / "bench/rates").mkdir()
    shutil.copy(BENCH / "reference/dense_gqa.py", root / "bench/reference/tiny_ref.py")
    (root / "bench/distributions/gamma_cv.py").write_text(GAMMA_CV)
    cfg = json.loads((BENCH / "configs" / "phi3-medium-14b-d10.json").read_text())
    cfg.update(name="tiny", hidden_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=256, vocab_size=500, reference="tiny_ref",
               engine={"n_slots": 4, "max_len": 256, "page_size": 16,
                       "prefill_chunk": 16})
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    chat.update(arrivals={"dist": "gamma_cv", "cv": 2.0},
                prompt_len={"dist": "lognormal", "median": 40, "sigma": 0.9,
                            "min": 8, "max": 128},
                output_len={"dist": "lognormal", "median": 16, "sigma": 0.8,
                            "min": 4, "max": 64}, check_tokens=40)
    (root / "bench/traffic/tinychat.json").write_text(json.dumps(chat))
    lc = json.loads((BENCH / "traffic" / "longctx.json").read_text())
    lc.update(clients=4, requests_per_client=60,
              prompt_len={"dist": "uniform", "min": 100, "max": 160},
              output_len={"dist": "uniform", "min": 40, "max": 90},
              first_wave_output_len={"dist": "uniform", "min": 1, "max": 90},
              check_tokens=60)
    (root / "bench/traffic/tinylong.json").write_text(json.dumps(lc))
    (root / "bench/rates/tiny.tinychat.json").write_text('{"rate_per_s": 3.0}')
    for cell in TINY_CELLS:
        (root / f"bench/checks/{cell}.json").write_text(
            json.dumps({"max_logit_gap": {"limit": TINY_LIMIT}}))
    (root / "bench/metrics/requests_finished.tiny.py").write_text(
        "def read(rec):\n"
        "    w = rec.window\n"
        "    return sum(1 for e in w.events.values()\n"
        "               if e.finish_s is not None and e.finish_s >= w.open_s)\n")
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "test"})
    b["workloads"] += [{"name": n, "config": "tiny", "traffic": n.split(".")[1],
                        "chips": 1, "why": "test"} for n in TINY_CELLS]
    # end-to-end metrics whose readers exist in bench/metrics, for the
    # tiny cells: new entries where BENCHMARK.json has none
    want = {"ttft_p90_s": ["tiny.tinychat"], "tbt_p99_s": list(TINY_CELLS),
            "output_tok_s": ["tiny.tinylong"]}
    have = {m["name"]: m for m in b["end_to_end"]}
    for name, cells in want.items():
        if name in have:
            have[name].setdefault("workloads", []).extend(cells)
        else:
            b["end_to_end"].append({"name": name, "unit": "s", "better": "lower",
                                    "bound": 0.25, "source": "host_clock",
                                    "workloads": cells})
    b["per_layer"].append({"name": "requests_finished.tiny", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "front end", "moves": "tbt_p99_s",
                           "workloads": list(TINY_CELLS)})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seed=3_000_000_019, trace=0, **kw):
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", "4", "--trace", str(trace)])
    return run.run_cell(args, root, require_chip=False, **kw)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.tinychat", {"setup_s", "ttft_p90_s", "tbt_p99_s"}),
    ("tiny.tinylong", {"setup_s", "tbt_p99_s", "output_tok_s"})])
def test_rehearsal_prints_the_result_keys(tiny_root, cell, metrics):
    result, _, _ = _run(tiny_root, cell)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == metrics
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    for check in line["checks"].values():
        assert set(check) == {"value", "limit"}


def test_new_metric_file_is_read_by_name(tiny_root):
    # requests_finished.tiny exists only as a new file and a new entry;
    # a traced run reads per-layer metrics (the device ones find no
    # device trace on the CPU, so the run is made with trace=0 and the
    # registry asked for the per-layer list directly)
    import registry
    bench = registry.load_benchmark(tiny_root)
    names = [m["name"] for m in registry.metrics_for(bench, "tiny.tinylong", True)]
    assert "requests_finished.tiny" in names
    read = registry.metric_reader("requests_finished.tiny", tiny_root)
    assert callable(read)


def test_cpu_run_fails_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "phi3-medium-14b-d10.longctx", "--seed", "5", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True, text=True,
                       timeout=300, cwd=REPO)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert "metrics" not in p.stdout and "{" not in p.stdout


def test_checkout_without_the_program_fails(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "phi3-medium-14b-d10.longctx", "--seed", "5", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       timeout=300, cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and "{" not in p.stdout


def test_new_reference_and_arrivals_are_read_by_name(tiny_root, monkeypatch):
    """The tiny configuration's reference and the tiny chat mix's arrival
    process exist only as new files; the run finds them by the names the
    data gives, and the reference judges the run correct."""
    loaded = []
    find = registry.module

    def spy(kind, name, root=registry.ROOT):
        loaded.append((kind, name))
        return find(kind, name, root)

    monkeypatch.setattr(registry, "module", spy)
    result, _, _ = _run(tiny_root, "tiny.tinychat", seed=2_900_000_021)
    assert result["correct"] is True, result["checks"]
    for want in [("reference", "tiny_ref"), ("distributions", "gamma_cv"),
                 ("generators", "open_loop")]:
        assert want in loaded, loaded


def test_a_token_altered_where_produced_is_not_correct(tiny_root):
    """The fault: every sampled token shifted by one, inside the step."""
    def shift(engine):
        sample, vocab = engine._sample, engine.model.cfg.vocab_size
        engine._sample = lambda *a, **k: (sample(*a, **k) + 1) % vocab
    result, checks, _ = _run(tiny_root, "tiny.tinychat", fault=shift)
    assert result["correct"] is False
    assert checks["max_logit_gap"]["value"] > TINY_LIMIT


def test_a_decode_step_that_leaves_its_state_unchanged_is_not_correct(tiny_root):
    """The fault: the decode step hands back the cache it was given, so
    no decoded token's keys and values are kept."""
    def stale(engine):
        forward = engine.model.forward

        def keep_cache(params, tokens, positions, mode=None, cache=None, **kw):
            logits, new, aux = forward(params, tokens, positions, mode=mode,
                                       cache=cache, **kw)
            return logits, (cache if mode == "decode" else new), aux
        engine.model.forward = keep_cache
    result, checks, _ = _run(tiny_root, "tiny.tinylong", fault=stale)
    assert result["correct"] is False
    assert checks["max_logit_gap"]["value"] > TINY_LIMIT


def test_fp8_control_is_not_correct(tiny_root):
    """The reference in fp8, in the program's place, comes out not
    correct by the harness's own comparison where the program comes out
    correct, on three seeds."""
    import correctness
    for seed in (11, 2_400_000_001, 7_000_000_003):
        result, checks, ctl = _run(tiny_root, "tiny.tinylong", seed=seed,
                                   control=True)
        assert result["correct"] is True, checks
        assert correctness.passed(ctl) is False, ctl
        assert ctl["max_logit_gap"]["value"] > TINY_LIMIT, ctl


def test_reference_matches_the_model_forward():
    """bench/reference against the program's own float32 forward at a
    reduced size (the reference imports none of the program)."""
    import jax
    import jax.numpy as jnp
    import model_setup
    from repro.models import build_model

    cfg = json.loads((BENCH / "configs" / "phi3-medium-14b-d10.json").read_text())
    cfg.update(hidden_size=128, num_hidden_layers=3, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, intermediate_size=256,
               vocab_size=300, torch_dtype="float32")
    dense_gqa = model_setup.reference(cfg)
    model = build_model(model_setup.model_config(cfg))
    params = model_setup.init_weights(model, 123)
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 300, 48), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = model.forward(params, toks[None], jnp.arange(48)[None])
        got = dense_gqa._forward(cfg, "f32", params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0, :, :300]),
                               atol=2e-4, rtol=2e-4)


def test_every_seed_gets_the_same_work():
    gen = registry.module("generators", "open_loop")
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    a = gen.make(chat, 5.0, 30.0, 1000, 1, registry.ROOT)
    b = gen.make(chat, 5.0, 30.0, 1000, 3_000_000_007, registry.ROOT)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps = lambda rs: sorted(np.round(np.diff([r.due_s for r in rs] + [30.0]), 9))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b))


def test_every_seed_gives_the_clients_the_same_sequences():
    gen = registry.module("generators", "closed_loop")
    lc = json.loads((BENCH / "traffic" / "longctx.json").read_text())
    a = gen.make(lc, None, 30.0, 1000, 1, registry.ROOT)
    b = gen.make(lc, None, 30.0, 1000, 3_000_000_007, registry.ROOT)
    lens = lambda qs: sorted(tuple((len(r.prompt), r.max_new_tokens) for r in q)  # noqa: E731
                             for q in qs)
    assert lens(a) == lens(b)
    assert [len(q[0].prompt) for q in a] != [len(q[0].prompt) for q in b]
    assert not np.array_equal(a[0][0].prompt[:50], b[0][0].prompt[:50])
    assert [r.client for q in a for r in q[:1]] == list(range(len(a)))


def test_unknown_device_has_no_peaks():
    from peaks import peaks_for
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


TRACE = BENCH / "tests" / "data" / "phi3_longctx_1s.xplane.pb"


def test_busy_time_is_the_union_of_overlapping_ops():
    import trace_reduce as tr
    merged = tr._union([(0, 4), (2, 6), (8, 9), (1, 3)])
    assert merged == [[0, 6], [8, 9]]
    assert tr._clip(merged, 5, 8.5) == [(5, 6), (8, 8.5)]


def test_reduction_of_a_trace_recorded_on_the_chip():
    """One second of phi3-medium-14b-d10.longctx traced on a TPU v5e:
    the reduction finds the device, the two jitted programs, the paged
    kernel inside them, and labels idle gaps by the benchmark's spans."""
    import trace_reduce as tr
    red = tr.reduce_trace(str(TRACE))
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    d_calls, d_secs = tr.module_time(red, "decode_step")
    p_calls, p_secs = tr.module_time(red, "prefill_row")
    assert d_calls > 0 and p_calls > 0
    assert d_secs + p_secs <= red["busy_s"] * 1.001
    assert 0 < red["kernel_s"] < d_secs + p_secs
    assert red["kernel_events"] % 10 == 0        # one call per layer
    assert red["idle_gaps"] and all(
        label in tr.HOST_SPANS + ("outside_spans",) for label, _ in red["idle_gaps"])
    assert len(red["device_ops"]) <= 10
