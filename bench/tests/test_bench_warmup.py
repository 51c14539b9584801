"""Set-up keeps the compile cache where the program keeps it."""
import jax

from benchkit import harness

MIN_SECS = "jax_persistent_cache_min_compile_time_secs"
MIN_BYTES = "jax_persistent_cache_min_entry_size_bytes"


def test_cache_goes_where_the_program_keeps_it(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = (getattr(jax.config, MIN_SECS), getattr(jax.config, MIN_BYTES))
    try:
        assert harness._place_cache() == str(tmp_path)
        assert getattr(jax.config, MIN_SECS) == 0
        assert getattr(jax.config, MIN_BYTES) == 0
    finally:
        jax.config.update(MIN_SECS, before[0])
        jax.config.update(MIN_BYTES, before[1])

