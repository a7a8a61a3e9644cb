"""Serving continuity — the lifetime-boundary resilience layer.

PRs 8/11/12 made a pipeline survive faults *within* a process lifetime;
this module makes the serving plane survive the lifetime boundary
itself. Three legs, each with an exact kill switch:

- **Epoch-based live reconfiguration** (:func:`swap_model`).
  ``Pipeline.swap_model(filter_name, model=..., weights=...)`` promotes
  the per-filter ``reload_model`` event to a pipeline-level *versioned*
  swap: the owning dispatch window drains (the fence is the cutover
  point — every in-flight batch completes against the old epoch), the
  new backend/params install under a bumped epoch, the affected fused
  region invalidates exactly once, and the next frame serves the new
  model. Zero frames are dropped because nothing is removed from the
  stream: frames dispatched before the cutover used the old program,
  frames after use the new one, so output is byte-identical up to the
  cutover seq. A params-only swap (``weights=``) is a consts swap —
  the fused executable is reused with no XLA recompile; a model swap
  re-jits exactly once. No swap call ⇒ none of this code runs.

- **Checkpoint / restore** (:func:`checkpoint` / :func:`restore`).
  Serializes the *durable serving state* a restarted process would
  otherwise re-learn from cold: tensor_repo slots (recurrent stream
  state), the SLO scheduler's service-rate EWMAs and AIMD knobs, the
  residency manager's LRU order, and the flight recorder's P² quantile
  markers + attribution ring. Armed by ``NNSTPU_CHECKPOINT=<dir>`` /
  ``--checkpoint-dir`` / ``Pipeline.checkpoint_dir``; unset means not
  one byte of this path executes (a single env read in start/stop).
  Monotonic-clock anchors (completion spacing, burn-window event
  times, controller step timers) are deliberately NOT restored — they
  are meaningless in a new process and re-anchor on the first
  observation.

- **Persistent compilation cache** (:func:`arm_compile_cache`).
  Arms JAX's persistent compilation cache so the second boot of the
  same pipeline performs zero XLA compilations on the serving path.
  Hits/misses surface as ``nns_compile_cache_hits_total`` /
  ``nns_compile_cache_misses_total`` via JAX's monitoring events; a
  per-fused-region program-signature manifest (``programs.json``)
  rides in the cache dir so operators can audit what the cache is
  keyed on. WHERE the cache lives is one rule
  (:func:`resolve_compile_cache_dir`): ``JAX_COMPILATION_CACHE_DIR``
  if the environment sets it (JAX reads it itself; this package never
  overrides it), else ``<checkout>/.jax_cache``. The directory is part
  of the cache key's stability — it never derives from a temp dir, a
  pid, a state dir or the clock. ``Pipeline.start()`` arms it when the
  variable is set or a checkpoint dir is armed.

See docs/robustness.md, "Serving continuity".
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Dict, Optional

from nnstreamer_tpu.log import get_logger

log = get_logger("continuity")

CHECKPOINT_ENV = "NNSTPU_CHECKPOINT"
#: JAX's own variable — read by jax.config at import, never set here
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: checkpoint state file name inside the checkpoint dir
STATE_FILE = "serving_state.pkl"
#: fused-region program-signature manifest inside the compile-cache dir
MANIFEST_FILE = "programs.json"
#: the fixed cache location when JAX_COMPILATION_CACHE_DIR is unset:
#: ``<checkout>/.jax_cache`` (git-ignored). An installed wheel has no
#: checkout (this is then the parent of site-packages): set the variable
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: state-file schema version — bump on any incompatible change
STATE_VERSION = 1

# --------------------------------------------------------------------------
# persistent compilation cache
# --------------------------------------------------------------------------
_cache_lock = threading.Lock()
_cache_dir: Optional[str] = None
_listener_installed = False
_metrics: Optional[Dict[str, Any]] = None

#: the JAX monitoring event names the hit/miss counters listen for
_EVENT_HIT = "/jax/compilation_cache/cache_hits"
_EVENT_MISS = "/jax/compilation_cache/cache_misses"


def cache_metrics() -> Dict[str, Any]:
    """Lazy shared counters (reads are safe from the listener thread)."""
    global _metrics
    if _metrics is None:
        with _cache_lock:
            if _metrics is None:
                from nnstreamer_tpu.obs import get_registry

                reg = get_registry()
                _metrics = {
                    "hits": reg.counter(
                        "nns_compile_cache_hits_total",
                        "XLA compilations served from the persistent "
                        "compile cache (warm boot: no compile happened)"),
                    "misses": reg.counter(
                        "nns_compile_cache_misses_total",
                        "XLA compilations the persistent cache could not "
                        "serve (a real compile ran and was written back)"),
                }
    return _metrics


def _on_jax_event(event: str, **kwargs) -> None:
    if event == _EVENT_HIT:
        cache_metrics()["hits"].inc()
    elif event == _EVENT_MISS:
        cache_metrics()["misses"].inc()


def compile_cache_dir() -> Optional[str]:
    """The armed cache directory, or None when the leg is off."""
    return _cache_dir


def cache_stats() -> Dict[str, int]:
    m = cache_metrics()
    return {"hits": int(m["hits"].value), "misses": int(m["misses"].value)}


def _env_cache_dir() -> str:
    return os.environ.get(JAX_CACHE_ENV, "").strip()


def resolve_compile_cache_dir() -> str:
    """THE cache-location rule: ``JAX_COMPILATION_CACHE_DIR`` when the
    environment sets it, else ``<checkout>/.jax_cache``. Imports no JAX,
    so a launcher parent (serving/fleet.py) can hand its children the
    same directory without touching a backend."""
    return _env_cache_dir() or DEFAULT_CACHE_DIR


def arm_compile_cache() -> str:
    """Arm JAX's persistent compilation cache where
    :func:`resolve_compile_cache_dir` says and return that directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already taken the
    directory from it (``jax.config`` reads the variable at import) and
    this function leaves that alone; only the unset case applies the
    fixed default. Idempotent — the rule has one answer per process.
    The size and compile-time floors are zeroed so CI-sized CPU programs
    persist too — the default floors exist to keep laptop caches small,
    but a serving cache wants every executable on the serving path."""
    global _cache_dir, _listener_installed
    with _cache_lock:
        if _cache_dir is not None:
            return _cache_dir
        import jax
        from jax._src import compilation_cache as _cc
        from jax._src import monitoring as _monitoring

        directory = resolve_compile_cache_dir()
        if not _env_cache_dir():
            try:
                os.makedirs(directory, exist_ok=True)
            except OSError as e:
                raise OSError(
                    f"compile cache: cannot create the default directory "
                    f"{directory} ({e}); set {JAX_CACHE_ENV} to place the "
                    f"cache elsewhere") from e
            jax.config.update("jax_compilation_cache_dir", directory)
        elif (held := jax.config.jax_compilation_cache_dir) != directory:
            # jax.config read the variable when jax was imported; set
            # later it names a directory JAX will never write, and
            # reporting it as armed would hide a cold cache
            raise RuntimeError(
                f"compile cache: {JAX_CACHE_ENV}={directory} was set after "
                f"jax was imported, and jax.config still holds {held!r}; "
                f"set the variable before the process starts")
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # JAX latches its use-the-cache decision at the first
        # compilation; arming after any jit has run (a warm import, an
        # earlier pipeline) would otherwise be silently inert
        _cc.reset_cache()
        if not _listener_installed:
            _monitoring.register_event_listener(_on_jax_event)
            _listener_installed = True
        _cache_dir = directory
    log.info("persistent compile cache armed at %s", directory)
    return directory


def maybe_arm_compile_cache(pipeline=None) -> Optional[str]:
    """``Pipeline.start()`` hook: arm the cache when the environment
    names a directory (``JAX_COMPILATION_CACHE_DIR``) or a checkpoint
    dir is armed (a process that persists serving state wants its
    programs warm too). Both unset ⇒ two env reads, nothing else runs."""
    if not (_env_cache_dir() or _effective_checkpoint_dir(pipeline)):
        return None
    try:
        return arm_compile_cache()
    except (OSError, RuntimeError) as e:  # an unusable cache dir must
        # not fail Pipeline.start() — serving continues cold, which is
        # exactly what an unarmed cache does, and says so
        log.warning("compile cache not armed: %s", e)
        return None


def region_signature(region) -> Dict[str, Any]:
    """A stable, auditable signature of one fused region's program: the
    member lineup plus the model/option properties that decide what gets
    traced. (The byte-exact cache key is XLA's own HLO hash — this
    manifest row is the operator-readable view of what maps to it.)"""
    members = []
    for m in getattr(region, "members", ()):
        members.append({
            "name": m.name,
            "type": getattr(m, "ELEMENT_NAME", type(m).__name__),
            "model": m._props.get("model"),
            "custom": m._props.get("custom"),
            "option": m._props.get("option"),
        })
    blob = json.dumps(members, sort_keys=True, default=str)
    return {
        "region": getattr(region, "name", "?"),
        "members": members,
        "signature": hashlib.sha256(blob.encode()).hexdigest()[:16],
    }


def write_program_manifest(pipe) -> Optional[str]:
    """Write the per-fused-region program-signature manifest into the
    armed cache dir. No cache dir or no regions ⇒ None."""
    directory = _cache_dir
    regions = [r for r in (getattr(pipe, "_regions", None) or ())
               if not getattr(r, "_dead", False)]
    if not directory or not regions:
        return None
    wall_written = time.time()  # export timestamp, not a duration
    doc = {
        "pipeline": pipe.name,
        "written_at": wall_written,
        "programs": [region_signature(r) for r in regions],
    }
    path = os.path.join(directory, MANIFEST_FILE)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)  # atomic publish
    return path


# --------------------------------------------------------------------------
# epoch-based live reconfiguration
# --------------------------------------------------------------------------
_swap_metric = None


def _count_swap() -> None:
    global _swap_metric
    if _swap_metric is None:
        from nnstreamer_tpu.obs import get_registry

        _swap_metric = get_registry().counter(
            "nns_model_swaps_total",
            "Pipeline-level live model swaps (epoch cutovers)")
    _swap_metric.inc()


def swap_model(pipe, filter_name: str, model: Optional[str] = None,
               weights: Any = None) -> Dict[str, Any]:
    """Zero-downtime versioned model swap on a running pipeline.

    Sequence: (1) drain the owning dispatch window — the fence is the
    cutover point, every in-flight batch completes against the old
    epoch; (2) install the new model/params under a bumped epoch (a
    weights-only swap re-registers the HBM residency unit under the new
    epoch key and retires the old epoch's unit, so ``nns_mem_used_bytes``
    nets out); (3) invalidate the owning fused region exactly once, so
    the next frame re-pulls stages — a params-only swap reuses the
    traced executable (no XLA recompile), a model-function swap re-jits
    once. Frames keep flowing throughout: nothing is dropped, output is
    byte-identical up to the cutover seq.
    """
    if model is None and weights is None:
        raise ValueError("swap_model: need model=, weights=, or both")
    el = pipe.by_name.get(filter_name)
    if el is None:
        raise KeyError(f"swap_model: no element {filter_name!r} in "
                       f"{pipe.name}")
    if not hasattr(el, "fw"):
        raise TypeError(f"swap_model: {filter_name!r} is not a "
                        f"tensor_filter")
    epoch = int(getattr(el, "_swap_epoch", 0)) + 1
    region = getattr(el, "_fused_region", None)
    if region is not None and getattr(region, "_dead", False):
        region = None

    # 1. fence: every outstanding dispatch against the old epoch retires
    #    before the new one installs — the cutover is between frames
    window = getattr(region if region is not None else el, "_window", None)
    if window is not None:
        window.drain()

    report: Dict[str, Any] = {
        "filter": filter_name, "epoch": epoch, "model": model,
        "weights": weights is not None, "invalidations": 0,
        "residency_unit": None, "retired_unit": None,
    }

    # 2. install under the new epoch
    fw = el.fw
    if model is not None:
        el._props["model"] = model
        if fw is not None:
            fw.handle_event("reload_model", {"model": model})
            el._obs_invoke()["reloads"].inc()
    if weights is not None:
        if fw is None:
            raise RuntimeError(f"swap_model: {filter_name!r} has no open "
                               f"backend to install weights into")
        install = getattr(fw, "install_weights", None)
        if install is None:
            raise RuntimeError(
                f"swap_model: backend {type(fw).__name__} does not "
                f"support in-place weight swaps")
        res = install(weights, epoch=epoch)
        report["residency_unit"] = res.get("residency")
        report["retired_unit"] = res.get("retired")

    # 3. exactly one fused-region invalidation: the next frame re-pulls
    #    member stages (consts swap in place, or one re-jit if the model
    #    function changed — nns_fuse_retraces_total counts that at trace
    #    time, never here)
    if region is not None:
        region.invalidate()
        report["invalidations"] = 1

    el._swap_epoch = epoch
    _count_swap()
    from nnstreamer_tpu.obs import timeline as _timeline

    tl = _timeline.ACTIVE
    if tl is not None:
        tl.mark("model_swap", None, track="continuity",
                filter=filter_name, epoch=epoch,
                consts_only=(model is None))
    log.info("%s: swapped %s to epoch %d (%s)", pipe.name, filter_name,
             epoch, "weights only" if model is None else model)
    return report


# --------------------------------------------------------------------------
# checkpoint / restore
# --------------------------------------------------------------------------
def _effective_checkpoint_dir(pipe, directory: Optional[str] = None
                              ) -> Optional[str]:
    if directory:
        return directory
    if pipe is not None and getattr(pipe, "checkpoint_dir", None):
        return pipe.checkpoint_dir
    env = os.environ.get(CHECKPOINT_ENV, "").strip()
    return env or None


def _query_servers(pipe):
    """Elements carrying a live query server (tensor_query_serversrc)."""
    out = []
    for el in getattr(pipe, "elements", ()):
        srv = getattr(el, "server", None) or getattr(el, "_server", None)
        if srv is not None and hasattr(srv, "checkpoint_state"):
            out.append((el.name, srv))
    return out


def checkpoint(pipe, directory: Optional[str] = None) -> str:
    """Serialize the pipeline's durable serving state into
    ``<dir>/serving_state.pkl`` (atomic publish) and refresh the
    program-signature manifest. Returns the state-file path."""
    directory = _effective_checkpoint_dir(pipe, directory)
    if not directory:
        raise ValueError(
            "checkpoint: no directory (pass one, set "
            "Pipeline.checkpoint_dir, or export NNSTPU_CHECKPOINT)")
    os.makedirs(directory, exist_ok=True)
    from nnstreamer_tpu.elements.repo import GLOBAL_REPO
    from nnstreamer_tpu.tensors import memory as _memory

    wall_saved = time.time()  # export timestamp, not a duration
    sched = getattr(pipe, "_slo_scheduler", None)
    fr = getattr(pipe, "_flight", None)
    acct = _memory.ACTIVE
    state: Dict[str, Any] = {
        "version": STATE_VERSION,
        "pipeline": pipe.name,
        "wall_saved": wall_saved,
        "repo": GLOBAL_REPO.snapshot(),
        "scheduler": sched.checkpoint_state() if sched is not None
        else None,
        "flight": fr.checkpoint_state() if fr is not None else None,
        "residency": acct.residency.checkpoint_state()
        if acct is not None else None,
        "servers": {name: srv.checkpoint_state()
                    for name, srv in _query_servers(pipe)},
        "swap_epochs": {el.name: int(el._swap_epoch)
                        for el in pipe.elements
                        if getattr(el, "_swap_epoch", 0)},
    }
    path = os.path.join(directory, STATE_FILE)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(state, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)  # atomic publish — a killed writer leaves the
    # previous checkpoint intact, never a torn one
    write_program_manifest(pipe)
    log.info("%s: checkpoint written to %s", pipe.name, path)
    return path


def restore(pipe, directory: Optional[str] = None) -> Dict[str, Any]:
    """Load ``<dir>/serving_state.pkl`` and re-arm the warm serving
    state: repo slots, scheduler estimates/knobs, residency LRU order,
    flight-recorder quantiles, query-server dedup windows and swap
    epochs. Returns a summary of what was applied."""
    directory = _effective_checkpoint_dir(pipe, directory)
    if not directory:
        raise ValueError(
            "restore: no directory (pass one, set "
            "Pipeline.checkpoint_dir, or export NNSTPU_CHECKPOINT)")
    path = os.path.join(directory, STATE_FILE)
    with open(path, "rb") as f:
        state = pickle.load(f)
    if state.get("version") != STATE_VERSION:
        raise ValueError(
            f"restore: state version {state.get('version')!r} != "
            f"{STATE_VERSION} (checkpoint from an incompatible build)")
    applied: Dict[str, Any] = {"path": path, "pipeline": state["pipeline"]}
    from nnstreamer_tpu.elements.repo import GLOBAL_REPO
    from nnstreamer_tpu.tensors import memory as _memory

    repo_state = state.get("repo")
    if repo_state:
        GLOBAL_REPO.restore(repo_state)
        applied["repo_slots"] = len(repo_state)
    sched = getattr(pipe, "_slo_scheduler", None)
    if sched is not None and state.get("scheduler"):
        sched.restore_state(state["scheduler"])
        applied["scheduler"] = True
    fr = getattr(pipe, "_flight", None)
    if fr is not None and state.get("flight"):
        fr.restore_state(state["flight"])
        applied["flight"] = True
    acct = _memory.ACTIVE
    if acct is not None and state.get("residency"):
        acct.residency.restore_state(state["residency"])
        applied["residency"] = True
    servers = dict(_query_servers(pipe))
    for name, srv_state in (state.get("servers") or {}).items():
        srv = servers.get(name)
        if srv is not None:
            srv.restore_state(srv_state)
            applied.setdefault("servers", []).append(name)
    for name, epoch in (state.get("swap_epochs") or {}).items():
        el = pipe.by_name.get(name)
        if el is not None:
            el._swap_epoch = int(epoch)
    log.info("%s: restored serving state from %s (%s)", pipe.name, path,
             ", ".join(k for k in applied if k not in ("path", "pipeline")))
    return applied


def maybe_restore_env(pipe) -> Optional[Dict[str, Any]]:
    """``Pipeline.start()`` hook: restore once from an armed checkpoint
    dir whose state file exists. Unset dir ⇒ one env read; armed dir
    with no state file (first boot) ⇒ one ``os.path.isfile``."""
    if getattr(pipe, "_continuity_restored", False):
        return None
    directory = _effective_checkpoint_dir(pipe)
    if not directory:
        return None
    path = os.path.join(directory, STATE_FILE)
    if not os.path.isfile(path):
        return None
    pipe._continuity_restored = True
    return restore(pipe, directory)


def maybe_checkpoint_on_stop(pipe) -> Optional[str]:
    """``Pipeline.stop()`` hook: write a checkpoint when armed. A
    failure to persist must never turn a clean shutdown into an error —
    it logs and returns None."""
    directory = _effective_checkpoint_dir(pipe)
    if not directory:
        return None
    try:
        return checkpoint(pipe, directory)
    except Exception as e:  # noqa: BLE001 — a full disk or unpicklable
        # payload must not fail teardown; the previous checkpoint (if
        # any) is still intact thanks to the atomic publish
        log.warning("%s: checkpoint on stop failed: %s", pipe.name, e)
        return None
