"""Docs-consistency check: every backtick-quoted dotted ``repro.*``
name in docs/ARCHITECTURE.md is a live API reference -- it must import
(module) or resolve by attribute walk (class / function / method).
Renaming or removing a public symbol without updating the architecture
doc fails this test, and with it CI."""
import importlib
import pathlib
import re

import pytest

DOC = pathlib.Path(__file__).resolve().parents[1] / "docs" / "ARCHITECTURE.md"
_SYM = re.compile(r"`(repro(?:\.[A-Za-z_][A-Za-z0-9_]*)+)`")


def _documented_symbols():
    # a missing doc must FAIL the exists-test below, not error pytest
    # collection (this function runs inside the parametrize decorator)
    if not DOC.is_file():
        return []
    return sorted(set(_SYM.findall(DOC.read_text())))


def _resolve(dotted: str):
    """Import the longest module prefix, then walk attributes."""
    parts = dotted.split(".")
    err = None
    for split in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:split]))
        except ImportError as e:
            err = e
            continue
        for attr in parts[split:]:
            obj = getattr(obj, attr)
        return obj
    raise ImportError(f"no importable prefix of {dotted!r}: {err}")


def test_architecture_doc_exists_and_names_symbols():
    assert DOC.is_file(), "docs/ARCHITECTURE.md is missing"
    syms = _documented_symbols()
    # the doc is only a consistency net if it actually names the API
    assert len(syms) >= 20, f"suspiciously few documented symbols: {syms}"


@pytest.mark.parametrize("dotted", _documented_symbols() or ["repro.plan"])
def test_documented_symbol_resolves(dotted):
    _resolve(dotted)  # raises ImportError / AttributeError on a stale doc


def test_streaming_construction_section_covers_api():
    """The 'Streaming plan construction' subsection must name the d-free
    build API (each name is then resolved by
    test_documented_symbol_resolves, so doc and code can't drift)."""
    syms = set(_documented_symbols())
    required = {
        "repro.core.wigner.wigner_window_iter",
        "repro.core.batched.plan_cache_stats",
        "repro.core.batched.streamed_rhs",
        "repro.core.batched.streamed_synthesis",
        "repro.core.batched.fft_analysis_slab",
        "repro.core.batched.SoftPlan.require_dense",
        "repro.kernels.ops.host_window_stack",
        "repro.kernels.ops.window_source",
        "repro.kernels.autotune.estimate_host_plan_bytes",
        "repro.kernels.autotune.PRECISION_BOUND_EXTRAPOLATED",
        "repro.plan.dense_table_bytes_limit",
    }
    missing = sorted(required - syms)
    assert not missing, f"ARCHITECTURE.md missing streaming symbols: {missing}"


def test_serving_section_covers_api():
    """The 'Serving tier' section must name the typed-shedding serving
    API (each name is then resolved by test_documented_symbol_resolves,
    so the doc and the service can't drift apart silently)."""
    syms = set(_documented_symbols())
    required = {
        "repro.so3.SO3Service",
        "repro.so3.SO3Service.submit",
        "repro.so3.SO3Service.close",
        "repro.so3.SO3Service.stats",
        "repro.so3.service.ServiceError",
        "repro.so3.service.Rejected",
        "repro.so3.service.Expired",
        "repro.so3.service.Cancelled",
        "repro.so3.result_key",
        "repro.plan.warm_bandwidths",
        "repro.obs.counter",
        "repro.launch.serve_so3",
    }
    missing = sorted(required - syms)
    assert not missing, f"ARCHITECTURE.md missing serving symbols: {missing}"


def test_observability_section_covers_obs_api():
    """The Observability section must name the repro.obs API (each name
    listed here is then resolved by test_documented_symbol_resolves, so
    the doc and the module can't drift apart silently)."""
    syms = set(_documented_symbols())
    required = {
        "repro.obs", "repro.obs.Recorder", "repro.obs.span",
        "repro.obs.time_fn", "repro.obs.get_recorder",
        "repro.obs.set_recorder", "repro.obs.check_chrome_trace",
        "repro.so3.CorrelationEngine.correlation_grids",
        "repro.obs.Recorder.add_span",
        "repro.obs.Recorder.dump_chrome_trace", "repro.obs.Recorder.rows",
        "repro.obs.Recorder.quantiles", "repro.launch.profile_so3",
    }
    missing = sorted(required - syms)
    assert not missing, f"ARCHITECTURE.md missing obs symbols: {missing}"
