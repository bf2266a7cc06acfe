"""Session (SparkSession-shaped) lifecycle tests."""

import pytest

from distributeddeeplearningspark_tpu import Session


def test_builder_local2(eight_devices):
    spark = Session.builder.master("local[2]").appName("t").getOrCreate()
    assert spark.app_name == "t"
    assert spark.num_devices == 2
    assert spark.default_parallelism == 2
    spark.stop()


def test_get_or_create_is_singleton(eight_devices):
    a = Session.builder.master("local[2]").getOrCreate()
    b = Session.builder.getOrCreate()
    assert a is b
    a.stop()
    c = Session.builder.master("local[4]").getOrCreate()
    assert c is not a
    assert c.num_devices == 4


def test_executor_instances_conf(eight_devices):
    spark = (
        Session.builder.config("spark.executor.instances", 4).getOrCreate()
    )
    assert spark.default_parallelism == 4
    assert spark.num_devices == 4


def test_mesh_conf_axes(eight_devices):
    spark = (
        Session.builder.master("local[2]")
        .config("mesh.fsdp", 2)
        .config("mesh.tensor", 2)
        .getOrCreate()
    )
    assert spark.mesh.shape["data"] == 2
    assert spark.mesh.shape["fsdp"] == 2
    assert spark.mesh.shape["tensor"] == 2
    assert spark.num_devices == 8


def test_master_too_large_raises(eight_devices):
    with pytest.raises(ValueError):
        Session.builder.master("local[16]").getOrCreate()


def test_parallelize_roundtrip(eight_devices):
    spark = Session.builder.master("local[2]").getOrCreate()
    rdd = spark.parallelize(range(10))
    assert rdd.num_partitions == 2
    assert rdd.collect() == list(range(10))
    assert spark.sparkContext is spark  # context == session


def test_context_manager(eight_devices):
    with Session.builder.master("local[2]").getOrCreate() as spark:
        assert spark.num_devices == 2
    with pytest.raises(RuntimeError):
        Session.active()


def test_master_tpu_refuses_cpu():
    """jax falls back to the host CPU with only a warning when it finds no
    TPU; a `tpu` session must not train there quietly."""
    with pytest.raises(ValueError, match="no TPU"):
        Session.builder.master("tpu").getOrCreate()
    with pytest.raises(RuntimeError):
        Session.active()


class _ConfigSpy:
    """Records the keys jax.config.update is called with."""

    def __init__(self, monkeypatch):
        import jax

        self.keys: list[str] = []
        real = jax.config.update

        def update(key, value):
            self.keys.append(key)
            return real(key, value)

        monkeypatch.setattr(jax.config, "update", update)


def test_compile_cache_env_set_code_sets_nothing(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and the program
    makes no jax_compilation_cache_dir update at all."""
    from distributeddeeplearningspark_tpu.utils import env

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    spy = _ConfigSpy(monkeypatch)
    assert env.configure_compile_cache() == "/x"
    with Session.builder.master("local[1]").getOrCreate():
        pass
    assert "jax_compilation_cache_dir" not in spy.keys


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    """Unset: the cache lands in <checkout>/.jax_cache, resolved from the
    package's own location — the same path in every process."""
    import os
    import subprocess
    import sys

    import jax

    from distributeddeeplearningspark_tpu.utils import env

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert env.configure_compile_cache() == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == env.DEFAULT_COMPILE_CACHE_DIR
    child_env = {k: v for k, v in os.environ.items()
                 if k != "JAX_COMPILATION_CACHE_DIR"}
    child_env["PYTHONPATH"] = repo + os.pathsep + child_env.get("PYTHONPATH", "")
    seen = {subprocess.run(
        [sys.executable, "-c",
         "import jax; from distributeddeeplearningspark_tpu.utils.env import "
         "configure_compile_cache as c; print(c()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, check=True, timeout=120,
        env=child_env, cwd=str(cwd)).stdout
        for cwd in (repo, os.path.dirname(repo))}
    assert seen == {env.DEFAULT_COMPILE_CACHE_DIR + "\n"
                    + env.DEFAULT_COMPILE_CACHE_DIR + "\n"}


def test_compile_cache_has_no_conf_key_override(monkeypatch, tmp_path):
    """The old spark.jax.compilationCache.dir key is gone: a job-scoped,
    moving directory never hits, so the conf cannot move the cache."""
    import jax

    from distributeddeeplearningspark_tpu.utils import env

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with (Session.builder.master("local[1]")
          .config("spark.jax.compilationCache.dir", str(tmp_path))
          .getOrCreate()):
        assert (jax.config.jax_compilation_cache_dir
                == env.DEFAULT_COMPILE_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == env.DEFAULT_COMPILE_CACHE_DIR
