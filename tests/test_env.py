"""Launcher perf hygiene (launch/env.py): pure env-dict mutations, the
--no-env-tuning escape hatch, and the one compilation-cache rule."""
import os

from repro.launch import env


def test_tuned_env_is_pure_and_sets_defaults():
    base = {}
    before = dict(base)
    out = env.tuned_env(base)
    assert base == before  # pure: the input dict is never mutated
    assert out["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert out["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] == \
        env.TCMALLOC_REPORT_THRESHOLD


def test_tuned_env_preserves_user_choices():
    base = {"TF_CPP_MIN_LOG_LEVEL": "0",
            "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "1",
            "LD_PRELOAD": "/opt/custom.so"}
    out = env.tuned_env(base)
    assert "TF_CPP_MIN_LOG_LEVEL" not in out
    assert "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" not in out
    assert "LD_PRELOAD" not in out  # user preload wins over tcmalloc


def test_tuned_env_never_touches_xla_flags():
    """jaxlib parses XLA_FLAGS once for every backend in the process and
    aborts on a flag it does not register, so the tuning adds none."""
    assert "XLA_FLAGS" not in env.tuned_env({})
    assert "XLA_FLAGS" not in env.tuned_env({"XLA_FLAGS": "--xla_foo=bar"})


def test_wants_tuning_escape_hatch(monkeypatch):
    assert env.wants_tuning(["prog", "--arch", "granite-8b"])
    assert not env.wants_tuning(["prog", "--no-env-tuning"])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere")
    for k in env.CACHE_THRESHOLDS:
        monkeypatch.setenv(k, "7")
    assert env.apply_from_argv(["prog", "--no-env-tuning"]) == {}


def test_compilation_cache_env_var_wins():
    """A cache directory the caller chose is used, and no other is set."""
    chosen = {"JAX_COMPILATION_CACHE_DIR": "/caller/cache"}
    out = env.compilation_cache_env(chosen)
    assert "JAX_COMPILATION_CACHE_DIR" not in out
    assert chosen == {"JAX_COMPILATION_CACHE_DIR": "/caller/cache"}  # pure
    # thresholds the caller set are kept too
    kept = env.compilation_cache_env(
        dict(chosen, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="5"))
    assert "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in kept


def test_compilation_cache_default_is_fixed_checkout_path():
    out = env.compilation_cache_env({})
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert out["JAX_COMPILATION_CACHE_DIR"] == os.path.join(root, ".jax_cache")
    # the same path every call: the directory is part of the cache lookup
    assert env.compilation_cache_env({}) == out
    # an empty variable counts as unset
    assert env.compilation_cache_env({"JAX_COMPILATION_CACHE_DIR": ""}) == out
    # thresholds zeroed so sub-second test compiles still hit the cache
    assert out["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
    assert out["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] == "-1"


def test_compilation_cache_is_independent_of_tuning_escape_hatch(monkeypatch):
    for k in ("JAX_COMPILATION_CACHE_DIR", *env.CACHE_THRESHOLDS):
        monkeypatch.delenv(k, raising=False)
    changes = env.apply_from_argv(["prog", "--no-env-tuning"])
    assert changes == env.compilation_cache_env({})
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(
        env.CHECKOUT / ".jax_cache")


def test_apply_mutates_target_and_reports_changes():
    target = {}
    changes = env.apply(target)
    assert changes and all(target[k] == v for k, v in changes.items())
    assert target["TF_CPP_MIN_LOG_LEVEL"] == "4"
    # second apply is a no-op on the already-tuned dict (except LD_PRELOAD,
    # which depends on whether the container ships tcmalloc)
    changes2 = {k: v for k, v in env.apply(target).items()
                if k != "LD_PRELOAD"}
    assert changes2 == {}


def test_find_tcmalloc_only_returns_existing_paths():
    tc = env.find_tcmalloc()
    assert tc is None or os.path.exists(tc)
    if tc is not None:
        assert "tcmalloc" in tc
