"""Persistent compile cache: a "second boot" must load compiled
programs from disk instead of recompiling.

The two-boot cycle is simulated in-process: ``jax.clear_caches()``
drops every in-memory jit executable (exactly what a restart loses)
while the on-disk cache survives, so re-running the same computation
must produce cache *hits* — the deterministic signal the cold-start CI
job and warmup report on.

The JAX cache knobs are process-global, so these tests share one cache
directory for the whole module and assert on counter deltas.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pilosa_tpu.parallel import compile_cache


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    import pathlib

    d = tmp_path_factory.mktemp("compile-cache")
    assert compile_cache.enable(str(d), stats=None)
    # The JAX cache dir is process-global and first-caller-wins; in a
    # full-suite run an earlier test's ServerNode may have enabled it
    # already — assert against whatever directory is actually live.
    return pathlib.Path(compile_cache.stats()["dir"])


def _cache_files(d):
    return [p for p in d.rglob("*") if p.is_file()]


def test_enable_reports_state(cache_dir):
    st = compile_cache.stats()
    assert st["enabled"]
    assert st["dir"] == str(cache_dir)


def _popcount_sum(x):
    bits = jnp.unpackbits(x.view(jnp.uint8), axis=-1)
    return bits.sum()


def test_second_boot_hits_disk_cache(cache_dir):
    # The cache key covers the lowered computation, which includes the
    # jit name — so "reboot" by re-jitting the SAME function, exactly
    # what a restarted planner does when it re-traces its kernels.
    x = jnp.asarray(np.arange(64, dtype=np.uint32))
    first = int(jax.jit(_popcount_sum)(x))
    assert _cache_files(cache_dir), "first boot must persist programs"
    before = compile_cache.stats()

    # "Restart": drop every in-memory executable, keep the disk cache.
    jax.clear_caches()

    second = int(jax.jit(_popcount_sum)(x))
    after = compile_cache.stats()
    assert second == first
    assert after["hits"] > before["hits"], (before, after)
    assert after["requests"] > before["requests"]


def test_stats_sink_fanout(cache_dir):
    class Sink:
        def __init__(self):
            self.counts = {}

        def count(self, name, n):
            self.counts[name] = self.counts.get(name, 0) + n

    sink = Sink()
    assert compile_cache.enable(str(cache_dir), stats=sink)
    try:
        def double(x):
            return x * 2

        y = jnp.asarray([1.0, 2.0])
        jax.jit(double)(y)
        jax.clear_caches()
        jax.jit(double)(y)
        assert sink.counts.get("compileCache.hits", 0) > 0
        assert sink.counts.get("compileCache.requests", 0) > 0
    finally:
        compile_cache.detach(sink)


def test_enable_without_dir_is_noop_query(cache_dir):
    # An empty dir ("off") never flips a live cache (first caller wins,
    # like the directory); it just answers whether the cache is on.
    assert compile_cache.enable("") is True


def test_planner_second_boot_reuses_programs(cache_dir):
    """End to end: a fresh MeshPlanner (new node, same machine) re-traces
    its kernels and the persistent cache serves them from disk."""
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.parallel import MeshPlanner, make_mesh

    mesh = make_mesh()

    def boot():
        h = Holder()
        idx = h.create_index("i")
        f = idx.create_field("f")
        f.import_bits([1] * 6, [s * SHARD_WIDTH + 3 for s in range(6)])
        ex = Executor(h, planner=MeshPlanner(h, mesh))
        return ex.execute("i", "Count(Row(f=1))")

    first = boot()
    before = compile_cache.stats()
    jax.clear_caches()
    second = boot()
    after = compile_cache.stats()
    assert second == first == [6]
    assert after["hits"] > before["hits"], (before, after)


@pytest.mark.parametrize("first, second, name", [
    ("Row(f=1)", "Row(g=4)", "count_tree_1"),
    ("Intersect(Row(f=1), Row(g=2))", "Intersect(Row(f=3), Row(g=4))",
     "count_tree_2"),
    ("Union(Row(f=1), Row(g=2), Row(f=3))",
     "Union(Row(g=4), Row(f=2), Row(g=1))", "count_tree_3"),
])
def test_program_named_by_class_not_by_rows(cache_dir, first, second, name):
    """A device program is named by its class and leaf count only: two
    plans of one class share one compiled program under one name, and
    the compile cache sees no new request when only the row ids change
    (the module's name is part of the persistent cache's key)."""
    from pilosa_tpu.config import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.parallel import MeshPlanner, make_mesh
    from pilosa_tpu.pql import parse

    h = Holder()
    idx = h.create_index("i")
    for fld in ("f", "g"):
        field = idx.create_field(fld)
        for row in (1, 2, 3, 4):
            field.import_bits([row] * 6,
                              [s * SHARD_WIDTH + 5 for s in range(6)])
    planner = MeshPlanner(h, make_mesh())
    shards = list(range(6))
    try:
        def run(pql):
            tree = parse(f"Count({pql})").calls[0].children[0]
            fn, arrays = planner.prepare_count(idx, tree, shards)
            return fn, arrays, planner.dispatch_count(fn, arrays).result()

        fn1, arrays, n1 = run(first)
        before = compile_cache.stats()["requests"]
        fn2, _, n2 = run(second)
        assert fn2 is fn1 and fn1.__name__ == name
        assert compile_cache.stats()["requests"] == before
        assert n1 == n2 == 6
        assert f"module @jit_{name}" in fn1.lower(*arrays).as_text()
    finally:
        planner.close()


# ------------------------------------------------------------------
# where the cache lives: placed from outside, or one fixed path
# ------------------------------------------------------------------


_ENV_DIR_CHECKS = {
    # a path given by flag: the variable wins, the program sets nothing
    "path": (
        "assert n.compile_cache_dir == outside, n.compile_cache_dir\n"
        "st = compile_cache.stats()\n"
        "assert st['enabled'] and st['dir'] == outside, st\n"
        "assert jax.config.jax_compilation_cache_dir == outside\n"
        "jax.jit(lambda x: x * 3)(jnp.arange(8)).block_until_ready()\n"
        "assert compile_cache.stats()['requests'] > 0\n"
        "assert os.listdir(outside), 'nothing persisted where asked'\n"),
    # "off" disables even there: JAX alone would still persist to it
    "off": (
        "assert n.compile_cache_dir == '', n.compile_cache_dir\n"
        "assert not compile_cache.stats()['enabled']\n"
        "ls = lambda: (os.path.exists(outside)\n"
        "              and sorted(os.listdir(outside)))\n"
        "before = ls()  # what an import compiled before 'off' was said\n"
        "jax.jit(lambda x: x * 3)(jnp.arange(8)).block_until_ready()\n"
        "assert ls() == before, (before, ls())\n"),
}


@pytest.mark.parametrize("flag", ["path", "off"])
def test_env_dir_wins_and_program_sets_no_other(tmp_path, flag):
    """With JAX_COMPILATION_CACHE_DIR set by the caller, neither an
    explicit compile_cache_dir nor the node default overrides it: JAX
    reads the variable itself and the program sets no directory of its
    own; only "off" switches the cache off. Process-global state, so a
    fresh interpreter."""
    import os
    import subprocess
    import sys
    import textwrap

    outside = tmp_path / "placed-from-outside"
    other = tmp_path / "explicit-flag"
    code = (
        "import os, sys\n"
        "import jax, jax.numpy as jnp\n"
        "from pilosa_tpu.parallel import compile_cache\n"
        "from pilosa_tpu.server.node import ServerNode\n"
        "outside, other, data = sys.argv[1:4]\n"
        "n = ServerNode(bind='127.0.0.1:0', data_dir=data,\n"
        "               compile_cache_dir=other)\n"
        "try:\n"
        + textwrap.indent(_ENV_DIR_CHECKS[flag], "    ") +
        "    assert not os.path.exists(other)\n"
        "    assert not os.path.exists(os.path.join(data, 'compile-cache'))\n"
        "finally:\n"
        "    n.close()\n"
        "print('env-dir-ok')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code, str(outside),
         "off" if flag == "off" else str(other), str(tmp_path / "data")],
        # Thresholds at which JAX alone would persist the tiny program,
        # so an empty directory under "off" means the cache is off.
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(outside),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                 JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=300, check=False)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "env-dir-ok" in proc.stdout


def test_default_dir_is_one_fixed_path_in_the_checkout(tmp_path,
                                                       monkeypatch):
    """Without the variable and without a flag, the cache lives at
    <checkout>/.jax_cache: never under the data dir (a fresh mkdtemp in
    most runs), because a directory that moves never hits."""
    import os

    from pilosa_tpu.server.node import ServerNode

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".jax_cache")
    assert compile_cache.DEFAULT_DIR == fixed
    assert compile_cache.resolve_dir(None) == fixed
    assert compile_cache.resolve_dir("") == fixed
    assert compile_cache.resolve_dir("off") == ""
    assert compile_cache.resolve_dir(str(tmp_path)) == str(tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    assert compile_cache.resolve_dir(str(tmp_path)) == "/placed/outside"
    assert compile_cache.resolve_dir("off") == ""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    for data_dir in (None, str(tmp_path / "data")):
        n = ServerNode(bind="127.0.0.1:0", data_dir=data_dir)
        try:
            assert n.compile_cache_dir == fixed
        finally:
            n.close()
    assert not (tmp_path / "data" / "compile-cache").exists()
