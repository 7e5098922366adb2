"""A store of serialized executables, so that a warm deploy LOADS a rung's
program instead of tracing and lowering it again.

With every executable found in JAX's persistent compile cache, a warm set-up
still traced each rung's program in Python and lowered it to MLIR, only to
compute the key under which the finished executable was then read back
(PERF.md §5: 1.1–4.5 s a rung).  This store keys an executable by what
determines the program WITHOUT a trace, so :class:`serving.rungs.RungPrograms`
asks it first and traces only on a miss.

**The key** (:func:`preimage`) holds everything that reaches the trace, the
lowering or the compile, and errs towards a miss: a digest of every ``.py``
file of this package (relative paths and contents, never the checkout's
path), the versions of JAX and jaxlib, the backend's platform and
``platform_version`` (the libtpu build), the device's kind, the device
count and the default backend (which decides whether a Pallas kernel is
compiled or interpreted), the scorer's own statics for the rung, the
abstract value (shape, dtype, sharding) of every argument the program is
lowered on, the JAX options that configure lowering and the environment's
``XLA_FLAGS``, ``LIBTPU_INIT_ARGS`` and ``PIO_*`` variables (all but
``PIO_STORAGE_*`` and ``PIO_FS_*``, which say where a deployment's stores
lie).

**What the key cannot see**: the digest reads source TEXT, not live
objects.  Code patched at run time (a monkeypatched function, an edited
module reloaded under the same file) is served the unpatched program.  So
the store engages only where JAX's own persistent cache does — enabled, and
with a directory — and tier-1 runs with that cache disabled
(``tests/conftest.py``); ``tools/verify_program_store.py`` lowers an entry's
program afresh and compares it with the text's digest kept at compile time.

**An entry** is one file, ``<key>.pgm``: a magic line, the length of a JSON
header, the header (the key's preimage, the sha256 of the lowered program's
text (kernels taken without their debug information) and the path of the
package that lowered it, the payload's sha256 and length) and the payload,
the pickled serialized executable with its two tree definitions, compressed
as JAX's cache compresses.  Written as a temporary file and
``os.replace``d, so two processes on one directory lose nothing.  Anything unreadable, truncated,
of another preimage or that fails to deserialize is a MISS, logged, never
an error at deploy: the rung compiles and the entry is written over.

The directory lies BESIDE JAX's cache directory (``<cache dir>-programs``),
never inside it, where JAX's own eviction counts bytes; it has one fixed cap
of its own, least recently used entries go first.
"""

from __future__ import annotations

import base64
import dataclasses
import functools
import hashlib
import json
import logging
import os
import pickle
import re
import struct
import threading
import uuid
from typing import Optional

import jax
import jaxlib

logger = logging.getLogger(__name__)

# two trees' ladders of all six benchmark cells (36 programs a tree at
# 2–7 MB compressed) fit three times over
CAP_BYTES = 1 << 30
_MAGIC = b"PIOPGM1\n"
_SUFFIX = ".pgm"
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what configures lowering from outside the code
_JAX_OPTIONS = ("jax_enable_x64", "jax_default_matmul_precision",
                "jax_numpy_dtype_promotion")
_ENV_NAMES = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
# every PIO_* variable is in the key but these: where ONE deployment keeps
# its event and model stores (paths and credentials, a temporary directory in
# every benchmark run), which no program is traced from and no entry's header
# should hold
_ENV_PLACES = ("PIO_STORAGE_", "PIO_FS_")


@functools.cache
def package_digest() -> str:
    """sha256 over every ``.py`` file of the package, relative path and
    contents, in sorted order; read once a process."""
    h = hashlib.sha256()
    paths = []
    for base, dirs, files in os.walk(_PACKAGE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        paths += [os.path.join(base, f) for f in files if f.endswith(".py")]
    for path in sorted(paths, key=lambda p: os.path.relpath(p, _PACKAGE)):
        rel = os.path.relpath(path, _PACKAGE).replace(os.sep, "/")
        with open(path, "rb") as f:
            body = f.read()
        h.update(f"{rel}\0{len(body)}\0".encode())
        h.update(body)
    return h.hexdigest()


def _abstract(x) -> list:
    sharding = getattr(x, "sharding", None)
    return [list(x.shape), str(x.dtype),
            None if sharding is None else str(sharding)]


def lowered_on(args) -> Optional[jax.Device]:
    """The ONE device ``args`` place the program on, or None: arguments on
    no device at all, or over a mesh of several (the sharded path), which
    the store leaves alone."""
    devices = set()
    for x in jax.tree_util.tree_leaves(args):
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            devices |= set(sharding.device_set)
    return devices.pop() if len(devices) == 1 else None


def preimage(statics: dict, args, device) -> dict:
    """What a rung's key is the digest of, JSON-able: see the module's
    docstring.  ``statics``: the scorer's description of the rung."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    return {
        "package": package_digest(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": device.client.platform,
        "platform_version": device.client.platform_version,
        "device_kind": device.device_kind,
        "device_count": jax.device_count(),
        # what `ops/pallas_mode.resolve` derives a kernel's mode from
        "default_backend": jax.default_backend(),
        "statics": statics,
        "args_tree": str(tree),
        "args": [_abstract(x) for x in leaves],
        "jax_options": {n: str(getattr(jax.config, n)) for n in _JAX_OPTIONS},
        "env": {n: v for n, v in sorted(os.environ.items())
                if n in _ENV_NAMES or (n.startswith("PIO_")
                                       and not n.startswith(_ENV_PLACES))},
    }


def _canonical(pre: dict) -> str:
    # default=repr: a value JSON cannot hold still reaches the key, and one
    # whose repr holds an address never matches: a miss, not a wrong hit
    return json.dumps(pre, sort_keys=True, default=repr)


def key_of(pre: dict) -> str:
    return hashlib.sha256(_canonical(pre).encode()).hexdigest()


# a Pallas kernel in a lowered program's text: the base64 of its Mosaic
# module's bytecode, inside the custom call's `backend_config`
_KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _without_debug_info(match) -> str:
    """A kernel's serialized body replaced by the digest of the module
    printed without locations; by the digest of the bytes as they are
    where this JAX will not parse them (a stricter answer, never a laxer)."""
    body = base64.b64decode(match.group(2))
    try:
        from jax._src.interpreters import mlir as _mlir
        from jax._src.lib.mlir import ir as _ir

        context = _mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            body = _ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False).encode()
    except Exception:  # jaxlib's internals moved: compare the bytes
        pass
    return match.group(1) + hashlib.sha256(body).hexdigest() + match.group(3)


def text_digest(lowered) -> str:
    """sha256 of a lowered program's text, what an entry keeps of the
    program it was compiled from (`tools/verify_program_store.py`).  A
    kernel's serialized body holds the call stack of the trace that made it
    — two callers of one program lower to different bytes — so each body
    is taken without its debug information; the text around them has none."""
    text = _KERNEL_BODY.sub(_without_debug_info, lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()


def config_statics(config) -> dict:
    """A model configuration as a key's ingredient: its class and every
    field."""
    return {"class": f"{type(config).__module__}.{type(config).__qualname__}",
            **dataclasses.asdict(config)}


def directory() -> Optional[str]:
    """Where the store lies now, or None where it does not engage: JAX's
    persistent compile cache must be enabled and have a directory (the
    operator's ``JAX_COMPILATION_CACHE_DIR`` or what
    ``parallel/mesh.configure_compile_cache()`` placed)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return None
    return os.path.normpath(str(cache_dir)) + "-programs"


def open_store() -> Optional["ProgramStore"]:
    """The store of this process's configuration, or None (see
    :func:`directory`; also where the directory cannot be made)."""
    root = directory()
    if root is None:
        return None
    try:
        os.makedirs(root, exist_ok=True)
    except OSError as e:
        logger.warning("program store: %s cannot be made (%s); compiling",
                       root, e)
        return None
    return ProgramStore(root)


def read_header(path: str) -> tuple[dict, int]:
    """An entry's JSON header and the offset of its payload; raises
    ``ValueError`` on a file that is no entry."""
    with open(path, "rb") as f:
        head = f.read(len(_MAGIC) + 4)
        if len(head) != len(_MAGIC) + 4 or not head.startswith(_MAGIC):
            raise ValueError("no program store entry")
        (n,) = struct.unpack("<I", head[len(_MAGIC):])
        raw = f.read(n)
    if len(raw) != n:
        raise ValueError("truncated header")
    return json.loads(raw), len(head) + n


class ProgramStore:
    """Entries under one directory; see the module's docstring."""

    def __init__(self, root: str, cap_bytes: int = CAP_BYTES):
        self.root = root
        self.cap_bytes = cap_bytes

    def path(self, pre: dict) -> str:
        return os.path.join(self.root, key_of(pre) + _SUFFIX)

    def entries(self) -> list[str]:
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return [os.path.join(self.root, n) for n in sorted(names)
                if n.endswith(_SUFFIX)]

    def load(self, pre: dict, device):
        """The executable stored under ``pre``, loaded onto ``device`` as a
        ``jax.stages.Compiled``; None on a miss of any kind."""
        from jax._src import compilation_cache as _jcc
        from jax.experimental import serialize_executable as _se

        path = self.path(pre)
        if not os.path.exists(path):
            return None
        try:
            header, offset = read_header(path)
            if _canonical(header["preimage"]) != _canonical(pre):
                raise ValueError("another preimage under this key")
            with open(path, "rb") as f:
                f.seek(offset)
                payload = f.read()
            if (len(payload) != header["payload_bytes"] or hashlib.sha256(
                    payload).hexdigest() != header["payload_sha256"]):
                raise ValueError("payload truncated or altered")
            serialized, in_tree, out_tree = pickle.loads(
                _jcc.decompress_executable(payload))
            loaded = _se.deserialize_and_load(
                serialized, in_tree, out_tree, backend=device.client,
                execution_devices=[device])
        except Exception as e:  # whatever the entry holds, deploy goes on
            logger.warning("program store: %s is a miss (%s: %s); compiling",
                           path, type(e).__name__, e)
            return None
        try:
            os.utime(path)  # most recently used
        except OSError:
            pass
        return loaded

    def save(self, pre: dict, compiled, lowered) -> int:
        """Keep ``compiled`` (what ``lowered.compile()`` gave) under
        ``pre``; returns the entry's bytes, 0 where it could not be kept."""
        from jax._src import compilation_cache as _jcc
        from jax.experimental import serialize_executable as _se

        path = self.path(pre)
        try:
            payload = _jcc.compress_executable(
                pickle.dumps(_se.serialize(compiled)))
            size = self._write(path, {
                "preimage": pre,
                "lowered_sha256": text_digest(lowered),
                # not in the key: for whoever has to find the writer
                "written_from": _PACKAGE,
            }, payload)
        except Exception as e:  # an executable that will not serialize, a
            # full disk: the program itself is compiled and serves
            logger.warning("program store: %s not written (%s: %s)",
                           path, type(e).__name__, e)
            return 0
        self._evict(keep=path)
        return size

    def _write(self, path: str, header: dict, payload: bytes) -> int:
        """One entry, whole or not at all: a temporary file, renamed."""
        raw = _canonical(dict(
            header, payload_sha256=hashlib.sha256(payload).hexdigest(),
            payload_bytes=len(payload))).encode()
        tmp = f"{path}.{os.getpid()}.{uuid.uuid4().hex}.tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(_MAGIC + struct.pack("<I", len(raw)) + raw)
                f.write(payload)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return len(_MAGIC) + 4 + len(raw) + len(payload)

    def total_bytes(self) -> int:
        return sum(size for _, size, _ in self._stat())

    def _stat(self) -> list[tuple[str, int, float]]:
        out = []
        for path in self.entries():
            try:
                st = os.stat(path)
            except OSError:  # another process evicted it
                continue
            out.append((path, st.st_size, st.st_mtime))
        return out

    def _evict(self, keep: str) -> None:
        entries = self._stat()
        total = sum(size for _, size, _ in entries)
        for path, size, _ in sorted(entries, key=lambda e: e[2]):
            if total <= self.cap_bytes:
                break
            if path == keep:
                continue
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size


class SavesBesideCompiles:
    """One deploy's writes to the store, kept off the ladder's wall.

    Serializing a sequence rung's executable holds the GIL for 0.26–0.32 s
    and its compression and write take 0.1 s more (PERF.md §6, PR 49):
    2–2.6 s a ladder if the thread that builds the ladder does it.  The
    backend's compile of the NEXT rung needs no GIL for its 10–15 s, so a
    rung's save waits (:meth:`add`) until the builder is about to enter one
    (:meth:`start`) and then runs beside it on a thread of its own; only
    the last rung's is waited for (:meth:`finish`).
    """

    def __init__(self, store: ProgramStore):
        self.store = store
        self._waiting: Optional[tuple] = None
        self._threads: list[threading.Thread] = []

    # add / start / finish are the ladder builder's alone: no save thread
    # touches ``_waiting`` or ``_threads``
    def add(self, pre: dict, compiled, lowered) -> None:
        self.start()
        waiting = (pre, compiled, lowered)
        self._waiting = waiting  # pio: ignore[race-unguarded-rebind]

    def start(self) -> None:
        if self._waiting is not None:
            # `ProgramStore.save` raises nothing: it logs and returns 0
            thread = threading.Thread(
                target=self.store.save, args=self._waiting,
                name="program-store-save")
            thread.start()
            self._threads.append(thread)
            self._waiting = None  # pio: ignore[race-unguarded-rebind]

    def finish(self) -> None:
        self.start()
        for thread in self._threads:
            thread.join()
        self._threads.clear()
