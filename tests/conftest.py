"""Shared fixtures: the serve shadow-state checker rides every serve test.

Serve-facing test modules run every ``ContinuousBatchingEngine`` they
build with the ``repro.analysis.schedcheck`` shadow state machine
attached (``check=True``), and assert at teardown that the checker saw a
clean transition history — refcounts conserved, no slot double-binds,
no leaked pages.  The failure-injection tests against bare ``PageTable``
/ ``PagedKVCache`` objects are unaffected: the checker attaches per
engine, not per table.
"""
import pytest

#: modules whose engines run under the shadow checker (the tier1 serve
#: surface: continuous engine, families parity, frontend, prefix cache,
#: sharded layouts, the speculative-decode driver, and the chip smoke
#: test's CPU rehearsal)
SERVE_TEST_MODULES = (
    "test_serve",
    "test_serve_families",
    "test_serve_frontend",
    "test_serve_prefix",
    "test_serve_sharded",
    "test_serve_spec",
    "test_serve_kv_stack",
    "test_spkv_decode",
    "test_chip_smoke",
)


@pytest.fixture(autouse=True)
def serve_shadow_checker(request, monkeypatch):
    mod = request.node.module.__name__.rpartition(".")[2]
    if mod not in SERVE_TEST_MODULES:
        yield
        return
    from repro.serve.engine import ContinuousBatchingEngine

    built = []
    orig_init = ContinuousBatchingEngine.__init__

    def init(self, *args, **kwargs):
        orig_init(self, *args, **kwargs)
        built.append(self)

    monkeypatch.setattr(ContinuousBatchingEngine, "_DEFAULT_CHECK", True)
    monkeypatch.setattr(ContinuousBatchingEngine, "__init__", init)
    yield
    errors = [f.format() for eng in built
              for f in eng.check_findings if f.severity == "error"]
    assert not errors, (
        "serve shadow-state checker flagged transitions:\n  "
        + "\n  ".join(errors))
