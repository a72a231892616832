"""Entry-point set-up: `--devices`, the compile cache, failing sections.

`--devices N` means a flow mesh over the first N devices of the platform
JAX runs on: host devices are forced only on the CPU, and a request for
more devices than the platform has is an error.  The persistent compile
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at a fixed
directory in the checkout.  A benchmark section that cannot be imported
fails the run instead of being skipped.
"""
import jax
import pytest

from repro.launch import devices


def test_request_devices_forces_host_devices_only_on_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=x")
    devices.request_devices(4)
    assert devices.os.environ["XLA_FLAGS"] == "--xla_dump_to=x"
    with pytest.raises(SystemExit, match="need >= 1"):
        devices.request_devices(0)


def test_request_devices_on_cpu_fails_once_jax_has_too_few(monkeypatch):
    # jax is already initialized in this process: the flag can no longer
    # take effect, so the request must fail loudly rather than be ignored
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit, match="already initialized"):
        devices.request_devices(jax.device_count() + 1)
    devices.request_devices(jax.device_count())


def test_flow_mesh_refuses_more_devices_than_the_platform_has():
    from repro.net.sender import flow_mesh

    n = jax.device_count()
    assert flow_mesh(n).devices.size == n
    with pytest.raises(ValueError, match=f"only {n}"):
        flow_mesh(n + 1)


def test_compile_cache_dir(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert devices.setup_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert devices.setup_compile_cache() == devices.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == devices.CACHE_DIR
        assert devices.CACHE_DIR.endswith(".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_unimportable_section_fails_the_run(monkeypatch):
    run = pytest.importorskip("benchmarks.run")
    monkeypatch.setattr(
        run, "SECTION_MODULES", [("broken", "bench_does_not_exist")]
    )
    with pytest.raises(ModuleNotFoundError):
        run._load_sections("broken")


def test_perf_row_tags_the_devices_its_program_ran_on():
    common = pytest.importorskip("benchmarks.common")

    n_rows = len(common.PERF_STATS)
    try:
        common.perf("one", fabric_ticks=8, path_decisions=4,
                    compile_s=1.0, run_s=1.0)
        common.perf("sharded", fabric_ticks=8, path_decisions=4,
                    compile_s=1.0, run_s=1.0, devices=4)
        assert [r["devices"] for r in common.PERF_STATS[n_rows:]] == [1, 4]
    finally:
        del common.PERF_STATS[n_rows:]
