"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §4 implication (c)): the
collectives layer is exercised on one host with
``--xla_force_host_platform_device_count=8``, mirroring the reference's
"distributed-without-a-cluster" pattern (``BaseTestDistributed``).

The suite pins the CPU: ``JAX_PLATFORMS=cpu`` and the device-count flag
are exported (so subprocesses inherit them) before jax is imported.  The
chip is driven by ``chip_smoke.py`` and ``benchmark/run.py``, never from
here.

The persistent compilation cache is switched off through JAX's own flag
(exported for subprocesses too), so no test writes into the checkout's
``.cache/xla`` — see ``deeplearning4j_tpu/parallel/compile_cache.py``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the same two settings in-process, for the case where a pytest plugin
# imported jax before this file ran
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` sweep — chaos "
        "replays and other multi-fit end-to-end runs that earn their "
        "keep in the composed smoke tools, not on every commit")
    config.addinivalue_line(
        "markers",
        "no_implicit_transfers: run the test under "
        "jax.transfer_guard('disallow') — any implicit host<->device "
        "transfer inside the test body fails it (hot-loop contract; see "
        "deeplearning4j_tpu/analysis/runtime.py)")
    config.addinivalue_line(
        "markers",
        "lockguard: run the test with instrumented threading locks — "
        "lock-order inversions and Eraser-style unguarded shared writes "
        "observed during the test fail it (see "
        "deeplearning4j_tpu/analysis/lockguard.py); DL4J_TPU_LOCKGUARD=1 "
        "applies the same check to every test in the session")
    config.addinivalue_line(
        "markers",
        "shardguard: run the test with runtime sharding-drift detection — "
        "any wrapped step dispatch whose array shardings differ from the "
        "placed NamedShardings (implicit resharding) fails the test (see "
        "deeplearning4j_tpu/analysis/shardguard.py); DL4J_TPU_SHARDGUARD=1 "
        "applies the same check to every test in the session")
    config.addinivalue_line(
        "markers",
        "strict_dtypes: run the test under "
        "jax.numpy_dtype_promotion('strict') — any implicit dtype "
        "promotion (e.g. a python float silently widening bf16 to fp32) "
        "inside the test body fails it (parity tests must pin dtypes "
        "explicitly, not inherit them from promotion rules)")


#: Three tests of ``tests/benchmark_tests/test_benchmark_looped.py`` (PR 32)
#: hold PR 32's entries to the LAST place of ``BENCHMARK.json``'s lists
#: (``configs[-1]``, ``workloads[-1]``, ``per_layer[-3:]``).  The manifest's
#: rule is that a PR APPENDS its entries, and that a file the benchmark
#: already has is edited by no PR but a ``benchmark`` one: so the next PR that
#: adds a configuration (PR 34) can neither keep these asserts true nor
#: repair them.  They are expected to fail from PR 34 on; what they hold
#: besides the position (the looped configuration's published numbers, its
#: cell's sizes, its metrics) is tested again, without the position, in
#: ``tests/benchmark_tests/test_benchmark_sparse.py``.  A ``benchmark`` PR
#: takes the three asserts out of the accepted file and this list with them
#: (PERF.md section 7).
LAST_PLACE_ASSERTS = {
    "tests/benchmark_tests/test_benchmark_looped.py::" + name for name in (
        "test_configuration_keeps_the_published_numbers",
        "test_cell_is_what_the_issue_names",
        "test_cell_reports_the_common_metrics_and_its_own")}


#: Two tests of ``tests/benchmark_tests/test_benchmark_sparse.py`` (PR 34)
#: hold the NUMBER of per-layer metrics the sparse and the looped cell report
#: (``len(names) == 14 + 6 + 6``, ``== 14 + 1 + 3``) and the sparse cell's
#: metrics beside ZAYA's to exactly PR 34's six.  A PR that appends a
#: per-layer metric to either cell (PR 36: ``unnamed_share.train`` and six
#: more) can neither keep those counts true nor repair an accepted file.  They
#: are expected to fail from PR 36 on; everything else they hold (the entries'
#: order and lists, the end-to-end metrics, the cells' own metrics) is tested
#: again, with the counts as they stand now, in
#: ``tests/benchmark_tests/test_benchmark_scope_rest.py``.  A ``benchmark`` PR
#: takes the counts out of the accepted file and this list with them.
METRIC_COUNT_ASSERTS = {
    "tests/benchmark_tests/test_benchmark_sparse.py::" + name for name in (
        "test_cell_reports_the_common_metrics_and_its_own",
        "test_looped_cell_reports_the_common_metrics_and_its_own")}


#: Three tests of ``tests/benchmark_tests/test_benchmark_scope_rest.py`` (PR 36)
#: hold PR 36's six per-layer entries to the END of ``per_layer``
#: (``order[first + 6:] == [...]``) and to exact ``workloads`` lists (the five
#: cells of ``unnamed_share.train``, the two of ``moe_share.combine.train``).
#: A PR that appends a cell which reports those metrics, and per-layer metrics
#: of its own (PR 38), can neither keep these asserts true nor repair an
#: accepted file.  They are expected to fail from PR 38 on; everything else
#: they hold (the six entries' order and fields, their accepted cells in
#: order, the sparse cell's own metrics) is tested again, without the
#: position and the lists' length, in
#: ``tests/benchmark_tests/test_benchmark_mla.py``.  A ``benchmark`` PR takes
#: the asserts out of the accepted file and this list with them.
APPENDED_AFTER_PR_36 = {
    "tests/benchmark_tests/test_benchmark_scope_rest.py::" + name for name in (
        "test_the_sparse_cell_reports_what_it_did_and_this_prs",
        "test_metric_files_and_manifest_entries[unnamed_share.train-scope_rest-"
        "args0-cells0]",
        "test_metric_files_and_manifest_entries[moe_share.combine.train-"
        "scope_split-args3-cells3]")}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in APPENDED_AFTER_PR_36:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="asserts that PR 36's entries close "
                "per_layer and list exactly the cells PR 36 knew; PR 38 "
                "appended a cell and its own metrics"))
        if item.nodeid in LAST_PLACE_ASSERTS:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="asserts that PR 32's entries are the "
                "LAST of BENCHMARK.json's lists; PR 34 appended its own"))
        if item.nodeid in METRIC_COUNT_ASSERTS:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="asserts the exact number of per-layer "
                "metrics a cell reports; PR 36 appended its own"))


@pytest.fixture(autouse=True)
def _transfer_guard_marker(request):
    """Enforce the ``no_implicit_transfers`` marker: the whole test body
    runs inside ``jax.transfer_guard("disallow")``, so hot-loop tests
    assert zero implicit transfers in addition to their own checks.  On
    the CPU backend this catches implicit host->device crossings (D2H is
    free there — full enforcement happens on real devices)."""
    if request.node.get_closest_marker("no_implicit_transfers") is None:
        yield
        return
    with jax.transfer_guard("disallow"):
        yield


@pytest.fixture(autouse=True)
def _lockguard_marker(request):
    """Enforce the ``lockguard`` marker (or ``DL4J_TPU_LOCKGUARD=1``
    session-wide): threading locks created during the test are
    instrumented, and any lock-order inversion or unguarded shared write
    the detector observes fails the test at teardown.  Tests that
    deliberately provoke violations drive their own ``LockGuard``
    instance instead of the marker."""
    from deeplearning4j_tpu.analysis import lockguard as lg

    if request.node.get_closest_marker("lockguard") is None \
            and not lg.enabled_from_env():
        yield
        return
    lg.LOCKGUARD.reset()
    lg.LOCKGUARD.install()
    try:
        yield
        violations = lg.LOCKGUARD.violations()
        assert not violations, lg.LOCKGUARD.report()
    finally:
        lg.LOCKGUARD.uninstall()
        lg.LOCKGUARD.reset()


@pytest.fixture(autouse=True)
def _shardguard_marker(request):
    """Enforce the ``shardguard`` marker (or ``DL4J_TPU_SHARDGUARD=1``
    session-wide): step dispatches through ``ShardGuard.wrap`` sites
    (trainer sync/ZeRO steps, serving decode) are diffed against their
    placed shardings, and any implicit resharding observed fails the
    test at teardown.  Tests that deliberately provoke violations drive
    their own ``ShardGuard`` instance instead of the marker."""
    from deeplearning4j_tpu.analysis import shardguard as sg

    if request.node.get_closest_marker("shardguard") is None \
            and not sg.enabled_from_env():
        yield
        return
    sg.SHARDGUARD.reset()
    sg.SHARDGUARD.enable()
    try:
        yield
        violations = sg.SHARDGUARD.violations()
        assert not violations, sg.SHARDGUARD.report()
    finally:
        sg.SHARDGUARD.disable()
        sg.SHARDGUARD.reset()


@pytest.fixture(autouse=True)
def _strict_dtypes_marker(request):
    """Enforce the ``strict_dtypes`` marker: the whole test body runs
    under ``jax.numpy_dtype_promotion("strict")``, so mixed-dtype ops
    raise instead of silently widening (the bf16-kernel parity tests
    must measure the kernel's arithmetic, not an accidental fp32
    upcast)."""
    if request.node.get_closest_marker("strict_dtypes") is None:
        yield
        return
    with jax.numpy_dtype_promotion("strict"):
        yield


@pytest.fixture
def rng_np():
    return np.random.default_rng(42)


@pytest.fixture
def cpu_peak_row(monkeypatch):
    """The peak table has no CPU row (a CPU utilization is not a device
    metric); tests that exercise the gauge plumbing bring their own."""
    from deeplearning4j_tpu.observability import cost

    monkeypatch.setitem(cost.PEAKS, "cpu", cost.DevicePeak(
        flops=5e10, bytes_per_s=2e10, hbm_bytes=0.0, source="test row"))


@pytest.fixture(autouse=True)
def _reset_observability(tmp_path):
    """The metrics registry, tracer and flight recorder are process-global
    singletons — wipe them (and restore the enable flag) after every test
    so counters recorded by one test can't satisfy another's assertions.
    The flight recorder's dump directory is pointed at the test's tmp dir
    for the DURATION of the test, so supervisor/serving tests that trip a
    dump never litter the repo working tree.  The cost-model cache is
    deliberately NOT cleared: signature hits persist across tests exactly
    as they do across steps in one process."""
    from deeplearning4j_tpu import observability as obs

    old_dump_dir = obs.FLIGHTREC.dump_dir
    obs.FLIGHTREC.dump_dir = tmp_path / "flightrec"
    yield
    obs.enable()
    obs.METRICS.reset()
    obs.TRACER.clear()
    obs.TRACER.stop_stream()
    obs.FLIGHTREC.clear()
    obs.FLIGHTREC.dump_dir = old_dump_dir
