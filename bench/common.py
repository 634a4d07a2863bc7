"""What every part of the benchmark shares: where the checkout is, how a
cell is resolved by name, the device and its peaks, the compile clock,
and the checks' part of the result line.

A cell is one entry of `workloads` in `BENCHMARK.json`. Everything about
it is found by name, each in a file of its own:

    bench/configs/<config>.json    sizes, deployment, precision, `system`,
                                   `program` (its `repro.configs` arch)
    bench/configs/<config>.py      the plain reference, weights from a seed
    bench/traffic/<traffic>.json   parameters the general generator reads
    bench/systems/<system>.py      what runs that kind of system
    bench/metrics/<metric>.py      one reader per per-layer metric
    bench/limits/<cell>.json       the limits of `correct`

The program's model is the architecture the configuration names, at the
configuration's sizes (`program_model`): a new configuration of an
architecture the program has is a data file, not code.

A metric's reader gets the reduced trace of the window, the chip's peaks,
the cell's configuration and traffic mix, and the system's raw record of
the window (for federated rounds: the rounds, each round's cohort and
every client's true size), and works out its own operations or bytes
from those shapes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"


class NoDevice(Exception):
    """The machine lacks the accelerator or the chips a cell asks for."""


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path: names of configs and metrics carry dots and
    dashes, which `import` cannot spell."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              str(path.relative_to(BENCH)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def resolve(cell_name: str, bench: Optional[Dict[str, Any]] = None
            ) -> Dict[str, Any]:
    """Everything a run of `cell_name` needs, found by name."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = load_json(ROOT / entry["file"])
    return {
        "cell": cell,
        "config": config,
        "reference": BENCH / "configs" / f"{cell['config']}.py",
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "system": BENCH / "systems" / f"{config['system']}.py",
        "end_to_end": [m for m in bench["end_to_end"]
                       if cell_name in m.get("workloads", [cell_name])],
        "per_layer": [m for m in bench["per_layer"]
                      if cell_name in m.get("workloads", [cell_name])],
    }


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py").read


# ------------------------------------------------------------------ program
# A configuration's published keys and the `ModelConfig` field each sets,
# as a dotted path into the nested configs.
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "num_labels": "num_classes",
    "tie_word_embeddings": "tie_embeddings",
    "first_k_dense_replace": "n_dense_layers",
    "num_attention_heads": "attention.n_heads",
    "num_key_value_heads": "attention.n_kv_heads",
    "head_dim": "attention.head_dim",
    "rope_theta": "attention.rope_theta",
    "qkv_bias": "attention.qkv_bias",
    "q_lora_rank": "attention.mla.q_lora_rank",
    "kv_lora_rank": "attention.mla.kv_lora_rank",
    "qk_nope_head_dim": "attention.mla.qk_nope_head_dim",
    "qk_rope_head_dim": "attention.mla.qk_rope_head_dim",
    "v_head_dim": "attention.mla.v_head_dim",
    "n_routed_experts": "moe.n_experts",
    "num_experts_per_tok": "moe.top_k",
    "moe_intermediate_size": "moe.d_ff_expert",
    "n_shared_experts": "moe.n_shared_experts",
}
SPLIT_LAYERS = ("head_layers", "body_layers", "tail_layers")


def _replace(obj, changes: Dict[str, Any], where: str):
    """`obj` with `changes` ({field: value or {field: ...}}) made, each
    nested config rebuilt once, so that a config checks itself only with
    all of its new values."""
    new = {}
    for name, value in changes.items():
        if isinstance(value, dict):
            inner = getattr(obj, name)
            if inner is None:
                raise ValueError(f"{where} has no {name} config to set "
                                 f"{sorted(value)} in")
            value = _replace(inner, value, f"{where}.{name}")
        new[name] = value
    return dataclasses.replace(obj, **new)


def program_model(config: Dict[str, Any], **split):
    """The program's `ModelConfig` and `SplitConfig` for a configuration.

    The architecture is the program's own, `repro.configs.get_config` of
    `config["program"]["arch"]`; every key of `PROGRAM_KEYS` the
    configuration gives then sets its field, and `program["fields"]`
    ({field path: value}) sets the program-only fields after those. Where
    the depth differs from the architecture's, the name says so, as
    `launch/serve.py` names it. The split's `head_layers`, `body_layers`
    and `tail_layers` are layers: the dense prefix is the head's, the rest
    are whole cycles of the layer pattern, and `SplitModel` has to give
    all three counts. Its other keys, and `split`, are `SplitConfig`
    fields."""
    from repro.configs import get_config
    from repro.core.split import SplitConfig, SplitModel

    base = get_config(config["program"]["arch"])
    changes: Dict[str, Any] = {}
    paths = [(PROGRAM_KEYS[k], v) for k, v in config.items()
             if k in PROGRAM_KEYS]
    paths += list(config["program"].get("fields", {}).items())
    for path, value in paths:
        *outer, last = path.split(".")
        node = changes
        for name in outer:
            node = node.setdefault(name, {})
        node[last] = value
    if changes.get("n_layers", base.n_layers) != base.n_layers:
        changes.setdefault("name", f"{base.name}-{changes['n_layers']}L")
    cfg = _replace(base, changes, base.name)

    sp = config["split"]
    cyc = len(cfg.layer_pattern)
    want = [sp[k] for k in SPLIT_LAYERS]
    patterned = [want[0] - cfg.n_dense_layers, want[2]]
    if min(patterned) < 0 or any(n % cyc for n in patterned + want[1:2]):
        raise ValueError(
            f"{cfg.name}: split {dict(zip(SPLIT_LAYERS, want))} is not "
            f"whole cycles of {cfg.layer_pattern} after "
            f"{cfg.n_dense_layers} dense prefix layers")
    split = SplitConfig(head_cycles=patterned[0] // cyc,
                        tail_cycles=patterned[1] // cyc,
                        **{k: v for k, v in sp.items()
                           if k not in SPLIT_LAYERS}, **split)
    model = SplitModel(cfg, split)
    got = [model.n_head_layers, model.n_body_layers, model.n_tail_layers]
    if got != want:
        raise ValueError(f"{cfg.name}: the split gives {got} head, body "
                         f"and tail layers, the configuration {want}")
    return cfg, split


# ------------------------------------------------------------- environment
def prepare_environment() -> str:
    """Before JAX starts: no compiler logs outside the checkout, and the
    persistent compilation cache at a fixed path inside it (or where
    JAX_COMPILATION_CACHE_DIR says). Returns the cache directory."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(n: int) -> List[Any]:
    """The TPU devices a cell runs on; NoDevice where JAX finds none or
    fewer than `n`."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"JAX finds no devices: {e}") from e
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU, only {devices[0].platform!r} "
                       "devices; this benchmark measures the chip")
    if len(devices) < n:
        raise NoDevice(f"the cell asks for {n} chips, JAX finds "
                       f"{len(devices)}")
    return devices[:n]


def peaks(device_kind: str) -> Dict[str, Any]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise NoDevice(f"device kind {device_kind!r} has no entry in "
                       f"bench/peaks.json; have {sorted(table)}")
    return table[device_kind]


def device_record(devices) -> Dict[str, Any]:
    """Platform, kind, count and the peak bytes on the fullest chip: the
    larger of the peak of live buffers and the peak reserved for programs'
    temporaries (a round program's show only in the second)."""
    peak = max(max(s.get("peak_bytes_in_use", 0),
                   s.get("peak_bytes_reserved", 0))
               for s in (d.memory_stats() or {} for d in devices))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def memory_note(devices) -> str:
    """Both peaks the runtime keeps: bytes of live buffers, and bytes
    reserved for programs' temporaries (a round program's show only in the
    second)."""
    stats = [d.memory_stats() or {} for d in devices]
    return ("memory: peak_bytes_in_use "
            f"{[s.get('peak_bytes_in_use') for s in stats]}, "
            f"peak_bytes_reserved {[s.get('peak_bytes_reserved') for s in stats]}")


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache loads
    included) and counts persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------- statistics
def check_line(checks: List[Dict[str, Any]]) -> Dict[str, Any]:
    """{name: {"value", "limit"}} for the result line's last key."""
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}


_JITTED: Dict[str, Any] = {}


def jit_once(fn, **static):
    """`jax.jit(partial(fn, **static))`, made once per process for each set
    of static arguments, so that a process that checks many seeds compiles
    each reference program once."""
    import functools
    import jax
    key = repr((fn.__module__, fn.__qualname__, sorted(static.items())))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(functools.partial(fn, **static))
    return _JITTED[key]


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from a seed of any size: the low and high 32 bits both
    count, so seeds past 2**32 stay distinct. `stream` separates the
    weights from the data and the traffic."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), stream)


# ------------------------------------------------------------------- a run
TRACE_SECONDS = 10.0   # a traced window lasts at most this long


class Window:
    """The measured window: counts the compiles inside it and, in a traced
    run, profiles it and reduces the trace."""

    def __init__(self, run: "Run"):
        self.run = run
        self.compiles_before = run.clock.compiles if run.clock else 0
        self.reduced: Optional[Dict[str, Any]] = None

    def __enter__(self):
        if self.run.trace:
            from bench import trace
            self._cm = trace.capture(str(self.run.trace_dir))
            self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        if self.run.trace:
            from bench import trace
            self._cm.__exit__(*exc)
            if exc[0] is None:
                devices, host = trace.load(str(self.run.trace_dir))
                self.reduced = trace.reduce(devices, host)
            shutil.rmtree(self.run.trace_dir, ignore_errors=True)
        return False


class Run:
    """What a system module gets: the cell's files, the seed, the window
    and the device. `plant` is a hook through which the benchmark's own
    tests break the timed path; a real run leaves it a no-op."""

    def __init__(self, *, config, traffic, ref, limits, seed, seconds,
                 trace=False, devices=(), clock=None, t_start=0.0,
                 plant=None):
        self.config, self.traffic, self.ref = config, traffic, ref
        self.limits, self.seed, self.seconds = limits, seed, seconds
        self.trace, self.devices, self.clock = trace, devices, clock
        self.t_start = t_start
        self.trace_dir = CACHE / "trace"
        self.plant = plant or (lambda *_: None)
        self.marks: Dict[str, float] = {}

    def mark(self, what: str) -> None:
        """Seconds from process start to the end of a part of set-up."""
        self.marks[what] = time.time() - self.t_start

    def window_seconds(self) -> float:
        return min(self.seconds, TRACE_SECONDS) if self.trace else self.seconds

    def window(self) -> Window:
        return Window(self)
