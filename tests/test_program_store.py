"""The program store (`serving/program_store.py`, ISSUE 49): a warm deploy
loads its rungs' executables instead of tracing and lowering them again.
The key moves with every ingredient and not with the checkout's path; a
loaded program is the compiled one to the bit; a bad entry is a miss; the
store stays out of every process where JAX's own persistent cache is off
(the state every other tier-1 test runs in: `tests/conftest.py`) and out of
the sharded path."""

import dataclasses
import os
import shutil
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from predictionio_tpu.serving import program_store as ps

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def store_on(tmp_path, monkeypatch):
    """JAX's persistent cache enabled with a directory under ``tmp_path``,
    as an operator's ``JAX_COMPILATION_CACHE_DIR`` gives it: the one state
    the store engages in.  Returns the store's own directory."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache = str(tmp_path / "jax-cache")
    # `MeshContext.create()` leaves the placement to the variable
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    before = (jax.config.jax_enable_compilation_cache,
              jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", cache)
    yield cache + "-programs"
    jax.config.update("jax_enable_compilation_cache", before[0])
    jax.config.update("jax_compilation_cache_dir", before[1])
    cc.reset_cache()


# -- the key --------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Config:
    vocab_size: int = 300
    hidden_size: int = 64


def _statics(**over):
    s = {"scorer": "PackedSequenceScorer", "family": "a.family",
         "config": ps.config_statics(_Config()), "rung": 256, "k": 100,
         "backend": "fused", "max_rows": 64}
    s.update(over)
    return s


def _args(shape=(4, 8), dtype=np.float32):
    dev = jax.devices()[0]
    return ({"w": jax.device_put(np.zeros(shape, dtype), dev)},
            jax.device_put(np.zeros(7, np.int32), dev))


def _package(root, body="x = 1\n", names=("a.py", "sub/b.py"), more=()):
    os.makedirs(os.path.join(root, "sub"))
    files = [(names[0], body), (names[1], "y = 2\n"),
             ("sub/notes.txt", "not source\n")] + [(m, "") for m in more]
    for rel, text in files:
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    return root


def _key(monkeypatch, package, statics=None, args=None):
    monkeypatch.setattr(ps, "_PACKAGE", package)
    ps.package_digest.cache_clear()
    args = _args() if args is None else args
    try:
        return ps.key_of(ps.preimage(
            _statics() if statics is None else statics, args,
            ps.lowered_on(args)))
    finally:
        ps.package_digest.cache_clear()


def _set_env(name, value):
    return lambda mp, tmp: mp.setenv(name, value) or {}


INGREDIENTS = {
    "source": lambda mp, tmp: {"package": _package(
        str(tmp / "edited"), body="x = 2\n")},
    "source_added": lambda mp, tmp: {"package": _package(
        str(tmp / "grown"), more=("c.py",))},
    "source_renamed": lambda mp, tmp: {"package": _package(
        str(tmp / "renamed"), names=("a2.py", "sub/b.py"))},
    "jax_version": lambda mp, tmp: mp.setattr(
        ps, "jax", _with_version(jax, "0.0.1")) or {},
    "jaxlib_version": lambda mp, tmp: mp.setattr(
        ps, "jaxlib", types.SimpleNamespace(__version__="0.0.1")) or {},
    "configuration": lambda mp, tmp: {"statics": _statics(
        config=ps.config_statics(_Config(hidden_size=128)))},
    "configuration_class": lambda mp, tmp: {"statics": _statics(
        config={**ps.config_statics(_Config()), "class": "other.Config"})},
    "family": lambda mp, tmp: {"statics": _statics(family="b.family")},
    "rung": lambda mp, tmp: {"statics": _statics(rung=512)},
    "k": lambda mp, tmp: {"statics": _statics(k=50)},
    "backend": lambda mp, tmp: {"statics": _statics(backend="reference")},
    "max_rows": lambda mp, tmp: {"statics": _statics(max_rows=32)},
    "arg_dtype": lambda mp, tmp: {"args": _args(dtype=np.float16)},
    "arg_shape": lambda mp, tmp: {"args": _args(shape=(4, 16))},
    "arg_on_host": lambda mp, tmp: {"args": (
        _args()[0], np.zeros(7, np.int32))},
    "pio_variable": _set_env("PIO_NATIVE", "0"),
    "xla_flags": _set_env(
        "XLA_FLAGS", os.environ.get("XLA_FLAGS", "") + " --xla_cpu_x=1"),
    "libtpu_init_args": _set_env("LIBTPU_INIT_ARGS", "--some=1"),
}


def _with_version(module, version):
    """``module`` as the store sees it, under another ``__version__``."""
    class _Proxy:
        __version__ = version

        def __getattr__(self, name):
            return getattr(module, name)
    return _Proxy()


@pytest.mark.parametrize("ingredient", sorted(INGREDIENTS))
def test_the_key_changes_with_each_ingredient(ingredient, monkeypatch,
                                              tmp_path):
    package = _package(str(tmp_path / "pkg"))
    base = _key(monkeypatch, package)
    assert base == _key(monkeypatch, package)  # and with nothing else
    changed = INGREDIENTS[ingredient](monkeypatch, tmp_path)
    assert _key(monkeypatch, changed.pop("package", package),
                **changed) != base


def test_the_key_does_not_change_with_the_checkouts_path(monkeypatch,
                                                         tmp_path):
    here = _package(str(tmp_path / "driver" / "checkout" / "pkg"))
    there = str(tmp_path / "pio" / "pkg")
    shutil.copytree(here, there)
    # compiled files and other data beside the source are not the source
    os.makedirs(os.path.join(there, "__pycache__"))
    open(os.path.join(there, "__pycache__", "a.cpython-312.py"), "w").close()
    assert _key(monkeypatch, here) == _key(monkeypatch, there)
    # an environment variable that is none of the named ones does not reach it
    base = _key(monkeypatch, here)
    monkeypatch.setenv("BENCH_RUN", "17")
    # nor where this deployment's stores lie (a temporary directory in every
    # run of the benchmark, credentials in a real one)
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "pio_store"))
    monkeypatch.setenv("PIO_STORAGE_SOURCES_META_PATH", str(tmp_path / "m.db"))
    assert _key(monkeypatch, here) == base


def test_the_real_packages_digest_is_of_its_source_alone():
    digest = ps.package_digest()
    assert len(digest) == 64 and digest == ps.package_digest()
    assert ps._PACKAGE == os.path.join(ROOT, "predictionio_tpu")


def _kernel_text(source, line, constant=1):
    """A lowered program's text around ONE kernel whose Mosaic module was
    traced at ``source:line``, as `Lowered.as_text()` spells it."""
    import base64
    import io
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    with mlir.make_ir_context():
        module = ir.Module.parse(
            f'module {{ func.func @k() -> i32 {{ %c = arith.constant '
            f'{constant} : i32 loc("{source}":{line}:1) return %c : i32 }} }}')
        out = io.BytesIO()
        module.operation.write_bytecode(out)
    body = base64.b64encode(out.getvalue()).decode()
    text = ('%0 = stablehlo.custom_call @tpu_custom_call(%arg0) {backend_config '
            '= "{\\22custom_call_config\\22: {\\22body\\22: \\22' + body +
            '\\22, \\22serialization_format\\22: 1}}", kernel_name = "k"}')
    return types.SimpleNamespace(as_text=lambda: text)


def test_the_text_digest_takes_a_kernel_without_the_call_stack_that_traced_it():
    """On a TPU a kernel's serialized body holds the trace's call stack: the
    deploy and `tools/verify_program_store.py` reach `_lower` by different
    callers, and the first chip run of the tool read every entry DIFFERENT
    for it."""
    cell = _kernel_text("/driver/checkout/benchmark/run.py", 431)
    tool = _kernel_text("/pio/tools/verify_program_store.py", 111)
    assert cell.as_text() != tool.as_text()
    assert ps.text_digest(cell) == ps.text_digest(tool)
    # another kernel is another digest, as is another program around it
    assert ps.text_digest(_kernel_text("/a.py", 1, constant=2)) \
        != ps.text_digest(cell)
    around = types.SimpleNamespace(as_text=lambda: cell.as_text() + " ")
    assert ps.text_digest(around) != ps.text_digest(cell)
    # a body this jaxlib will not parse is compared as the bytes it is
    odd = [types.SimpleNamespace(as_text=lambda b=b: cell.as_text().replace(
        "TUzvUg", b)) for b in ("AAAAAA", "BBBBBB")]
    assert ps.text_digest(odd[0]) != ps.text_digest(odd[1])


# -- through the scorers ----------------------------------------------------------

def _packed():
    from predictionio_tpu.models import latent_moe as model
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    cfg = model.LatentMoEConfig.from_hf(dict(
        vocab_size=300, hidden_size=64, num_hidden_layers=2,
        intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=8, num_experts_per_tok=2, num_attention_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16), max_len=64)
    sc = PackedSequenceScorer(cfg, model.init_params(cfg, 49), max_k=5,
                              ladder=(64, 128), max_rows=4)
    rng = np.random.default_rng(49)

    def rung_args(t):
        rows = [rng.integers(0, cfg.vocab_size, t // 4).astype(np.int32)
                for _ in range(3)]
        return sc._call_args(rows, t)
    served = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
              for n in (5, 20, 60, 64, 20)]  # two dispatches
    return sc, sc.ladder, rung_args, lambda: sc.score_topk(served, 5)


def _one_device_ctx():
    from predictionio_tpu.parallel.mesh import MeshContext

    return MeshContext.create(devices=jax.devices()[:1])


def _bucketed(ctx=None, **kw):
    from predictionio_tpu.serving.fastpath import BucketedScorer

    rng = np.random.default_rng(5)
    sc = BucketedScorer(
        ctx or _one_device_ctx(),
        rng.normal(size=(40, 6)).astype(np.float32),
        rng.normal(size=(29, 6)).astype(np.float32), max_k=5, **kw)
    users = np.random.default_rng(6)
    served = np.arange(70, dtype=np.int32) % 40  # over the top rung
    return (sc, sc.buckets, lambda b: sc._call_args(
        users.integers(0, 40, b).astype(np.int32)),
        lambda: sc.score_topk(served, 5))


def _every_output(sc, ladder, rung_args, serve):
    """Every output of every rung on a seeded input (and a served answer,
    through the whole dispatch path), and the counters of the set-up."""
    stats = sc._rungs.stats()
    outs = {r: jax.tree_util.tree_leaves(sc._rungs.direct(r, rung_args(r)))
            for r in ladder}
    outs["served"] = list(serve())
    return outs, stats


def _assert_equal_to_the_bit(got, want):
    assert got.keys() == want.keys()
    for r in want:
        assert len(got[r]) == len(want[r]) > 0
        for a, b in zip(got[r], want[r]):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("build", [_packed, _bucketed])
def test_a_scorer_built_twice_on_one_store_loads_every_rung(build, store_on):
    first, s1 = _every_output(*build())
    n = s1["compile_count"]
    assert s1["programs_loaded"] == 0 and n == len(first) - 1 > 1
    store = ps.ProgramStore(store_on)
    assert len(store.entries()) == n
    for path in store.entries():
        header, offset = ps.read_header(path)
        assert os.path.basename(path) == ps.key_of(header["preimage"]) + ".pgm"
        assert len(header["lowered_sha256"]) == 64
        assert os.path.getsize(path) == offset + header["payload_bytes"]
    assert store.total_bytes() == sum(map(os.path.getsize, store.entries()))
    sc, *rest = build()
    second, s2 = _every_output(sc, *rest)
    assert s2["programs_loaded"] == s2["compile_count"] == n
    assert s2["warmup_executions"] == s1["warmup_executions"]
    _assert_equal_to_the_bit(second, first)
    # what the scorers ask of a compiled program, a loaded one answers
    for fn in sc._fns.values():
        assert fn.memory_analysis() is not None
    assert len(store.entries()) == n  # and a load writes nothing


def _damage(kind, path):
    size = os.path.getsize(path)
    if kind == "truncated":
        with open(path, "r+b") as f:
            f.truncate(size - 7)
    elif kind == "truncated_header":
        with open(path, "r+b") as f:
            f.truncate(20)
    elif kind == "garbage":
        with open(path, "wb") as f:
            f.write(os.urandom(size))
    elif kind == "payload_altered":
        with open(path, "r+b") as f:
            f.seek(size - 100)
            f.write(b"\0" * 50)
    elif kind == "empty":
        open(path, "wb").close()


@pytest.mark.parametrize("kind", ["truncated", "truncated_header", "garbage",
                                  "payload_altered", "empty",
                                  "wrong_preimage", "not_an_executable"])
def test_a_bad_entry_is_a_miss_that_ends_in_a_correct_program(
        kind, store_on, caplog):
    want, _ = _every_output(*_bucketed())
    store = ps.ProgramStore(store_on)
    paths = store.entries()
    victim, other = paths[0], paths[1]
    if kind == "wrong_preimage":
        # a sound entry of ANOTHER program under this key
        shutil.copyfile(other, victim)
    elif kind == "not_an_executable":
        header, offset = ps.read_header(victim)
        _rewrite_payload(victim, header, b"no pickle at all")
    else:
        _damage(kind, victim)
    with caplog.at_level("WARNING", logger=ps.__name__):
        got, stats = _every_output(*_bucketed())
    assert stats["programs_loaded"] == stats["compile_count"] - 1
    assert sum("is a miss" in r.getMessage() for r in caplog.records) == 1
    _assert_equal_to_the_bit(got, want)
    # the miss compiled and wrote the entry over: the next deploy loads all
    _, stats = _every_output(*_bucketed())
    assert stats["programs_loaded"] == stats["compile_count"]


def _rewrite_payload(path, header, payload):
    from jax._src import compilation_cache as jcc

    ps.ProgramStore(os.path.dirname(path))._write(
        path, {k: header[k] for k in ("preimage", "lowered_sha256",
                                      "written_from")},
        jcc.compress_executable(payload))


def test_an_edit_to_the_package_is_a_miss(store_on, monkeypatch, tmp_path):
    _, s1 = _every_output(*_bucketed())
    monkeypatch.setattr(ps, "_PACKAGE", _package(str(tmp_path / "pkg")))
    ps.package_digest.cache_clear()
    try:
        _, s2 = _every_output(*_bucketed())
    finally:
        ps.package_digest.cache_clear()
    assert s1["programs_loaded"] == s2["programs_loaded"] == 0
    assert len(ps.ProgramStore(store_on).entries()) == 2 * s1["compile_count"]


def test_the_cap_evicts_the_least_recently_used(store_on):
    _every_output(*_bucketed())
    store = ps.ProgramStore(store_on)
    paths = store.entries()
    sizes = {p: os.path.getsize(p) for p in paths}
    for age, p in enumerate(paths):  # paths[0] the oldest
        os.utime(p, (1_000_000 + age, 1_000_000 + age))
    # room for all but one; the entry just written always stays
    small = ps.ProgramStore(
        store_on, cap_bytes=sum(sizes.values()) - sizes[paths[0]])
    os.utime(paths[1], (999_000, 999_000))  # now the oldest of all
    small._evict(keep=paths[1])
    assert small.entries() == [p for p in paths if p != paths[0]]
    small.cap_bytes = 0
    small._evict(keep=paths[2])
    assert small.entries() == [paths[2]]


def test_a_save_waits_for_the_next_compile_and_runs_on_a_thread_of_its_own():
    import threading

    log = []

    class _Store:
        def save(self, pre, compiled, lowered):
            log.append((pre, threading.current_thread().name))

    saves = ps.SavesBesideCompiles(_Store())
    saves.add("rung 1", None, None)
    assert log == []  # nothing to run beside yet
    saves.start()  # the builder is about to compile rung 2
    saves.add("rung 2", None, None)
    saves.finish()  # the last rung's is waited for
    assert [pre for pre, _ in log] == ["rung 1", "rung 2"]
    assert {name for _, name in log} == {"program-store-save"}
    assert threading.current_thread().name != "program-store-save"
    saves.finish()  # and nothing is written twice
    assert len(log) == 2


WRITER = """
import os, sys
import jax, numpy as np
from predictionio_tpu.serving import program_store as ps
root, me = sys.argv[1], int(sys.argv[2])
store = ps.ProgramStore(root)
dev = jax.devices()[0]
for i in range(12):
    # six keys both processes write, six each writes alone
    name = i if i < 6 else 100 * (me + 1) + i
    x = jax.device_put(np.zeros(8 + name % 50, np.float32), dev)
    lowered = jax.jit(lambda x: x * 2 + 1).lower(x)
    pre = ps.preimage({"n": name}, (x,), dev)
    assert store.save(pre, lowered.compile(), lowered) > 0
    assert store.load(pre, dev) is not None
print("written", flush=True)
"""


def test_two_processes_writing_one_directory_lose_nothing(tmp_path):
    root = str(tmp_path / "shared-programs")
    os.makedirs(root)
    env = dict(os.environ, PYTHONPATH=ROOT)
    procs = [subprocess.Popen(
        [sys.executable, "-c", WRITER, root, str(me)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for me in (0, 1)]
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0 and "written" in out, err[-2000:]
    store = ps.ProgramStore(root)
    assert len(store.entries()) == 18  # 6 shared + 2 x 6, no temporary left
    assert sorted(os.listdir(root)) == [
        os.path.basename(p) for p in store.entries()]
    dev = jax.devices()[0]
    for path in store.entries():
        header, _ = ps.read_header(path)
        pre = header["preimage"]
        x = np.arange(pre["args"][0][0][0], dtype=np.float32)
        loaded = store.load(pre, dev)
        np.testing.assert_array_equal(np.asarray(loaded(x)), x * 2 + 1)


# -- where it does not engage -------------------------------------------------------

@pytest.mark.parametrize("state", ["disabled", "no_directory", "unwritable"])
def test_without_jaxs_cache_the_scorer_compiles_as_the_parent_did(
        state, tmp_path, monkeypatch):
    """`tests/conftest.py` disables JAX's cache for every tier-1 test: the
    state `disabled` is the one this whole suite runs in."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = (jax.config.jax_enable_compilation_cache,
              jax.config.jax_compilation_cache_dir)
    cache = tmp_path / "jax-cache"
    try:
        if state == "disabled":
            assert before[0] is False
            jax.config.update("jax_compilation_cache_dir", str(cache))
            assert ps.directory() is None and ps.open_store() is None
        elif state == "no_directory":
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_dir", None)
            assert ps.directory() is None and ps.open_store() is None
        else:
            # the store's place is taken by a FILE: nowhere, not an error
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_dir", str(cache))
            (tmp_path / "jax-cache-programs").write_text("in the way")
            assert ps.directory() == str(cache) + "-programs"
            assert ps.open_store() is None
        # `MeshContext.create()` must not move the directory meanwhile
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
        for build in (_packed, _bucketed):
            _, stats = _every_output(*build())
            assert stats["programs_loaded"] == 0
            assert stats["compile_count"] == len(stats["bucket_hits"])
    finally:
        jax.config.update("jax_enable_compilation_cache", before[0])
        jax.config.update("jax_compilation_cache_dir", before[1])
        cc.reset_cache()
    assert not (tmp_path / "jax-cache-programs").is_dir()


def test_the_sharded_path_and_a_mesh_of_several_devices_never_touch_the_store(
        store_on):
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.serving import sharding

    ctx = MeshContext.create()
    assert ctx.n_devices > 1
    # replicated over the mesh's eight devices: lowered for more than one
    for _ in range(2):
        _, stats = _every_output(*_bucketed(ctx))
        assert stats["programs_loaded"] == 0
    plan = sharding.build_plan(29, 4)
    for _ in range(2):
        sc, *_ = _bucketed(ctx, plan=plan, sharding="sharded")
        assert sc.sharding == "sharded"
        assert sc.stats()["programs_loaded"] == 0
    assert not os.path.isdir(store_on) or not os.listdir(store_on)
