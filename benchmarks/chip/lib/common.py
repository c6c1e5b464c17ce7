"""What every cell shares: finding its files, the device, seeds, the compile
clock, and the result line."""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]     # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                         # the checkout
# fixed, inside the checkout: the path is part of the cache key
CACHE_DIR = BENCH_DIR / ".jax_cache"
OUT_DIR = BENCH_DIR / ".out"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it names."""
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    config_name: str


def cell_from_files(config: str, traffic: str, chips: int = 1) -> Cell:
    """A cell of ``configs/<config>.json`` under ``traffic/<traffic>.json``,
    whether or not ``BENCHMARK.json`` lists it yet."""
    return Cell(name=f"{config}.{traffic}", chips=chips,
                config=load_json(BENCH_DIR / "configs" / f"{config}.json"),
                traffic=load_json(BENCH_DIR / "traffic" / f"{traffic}.json"),
                config_name=config)


def find_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
            return Cell(name=name, chips=w["chips"],
                        config=load_json(ROOT / cfg["file"]),
                        traffic=load_json(BENCH_DIR / "traffic"
                                          / f"{w['traffic']}.json"),
                        config_name=w["config"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(cell: str, kind: str, bench: Optional[dict] = None
                ) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    bench = bench or benchmark()
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def setup_compile_cache() -> None:
    """Keep JAX's persistent cache inside the checkout, whatever the
    machine's environment says, and cache every program."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; raises ``NoChip``
    without a TPU or with fewer chips than the cell asks for."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no device: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def free(tree) -> None:
    """Release the device memory of every array in ``tree`` now, whatever
    still refers to it (compiled steps keep their owners alive)."""
    import jax
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = load_json(BENCH_DIR / "lib" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "lib/peaks.json")
    return table[device_kind]


def jax_key(seed: int):
    """A JAX key from any non-negative seed below 2**63 (seeds may run past
    32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed >> 32), seed & 0xFFFFFFFF)


class CompileClock:
    """Seconds spent in XLA compiles (persistent-cache loads included),
    persistent-cache hits and compile count, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class RunResult:
    """What a cell hands back to ``run.py``."""
    end_to_end: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    notes: List[str]
    # what the per-layer readers see (traced runs only)
    layer_ctx: dict = dataclasses.field(default_factory=dict)
    breakdown: Optional[dict] = None
    busy_s: Optional[float] = None
    window_s: Optional[float] = None


_PREFIX = [""]


def set_log_prefix(device: dict) -> None:
    """Every later log line names the platform, device kind and count."""
    _PREFIX[0] = f"[{device['platform']} {device['kind']} x{device['count']}] "


def log(msg: str) -> None:
    print(_PREFIX[0] + msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    """A module from a file whose name need not be an identifier
    (``configs/bert-large.py``, ``metrics/mfu.train.py``)."""
    import importlib.util
    name = "chipbench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(BENCH_DIR)))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str) -> Callable[[dict], Optional[float]]:
    """``metrics/<name>.py``'s ``read(ctx)``: the metric, or None where the
    run has nothing to read for it."""
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py").read


def reference_module(cell: "Cell"):
    """The configuration's plain reference, ``configs/<config>.py``."""
    return load_module(BENCH_DIR / "configs" / f"{cell.config_name}.py")


def program_arch(cfg: dict):
    """The program's architecture with every size the configuration file
    states written in, so the run is the file's configuration."""
    import dataclasses as dc

    from repro.configs import get_config
    base = get_config(cfg["program_arch"])
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    sizes = dict(num_layers=cfg["num_hidden_layers"], d_model=d,
                 num_heads=h,
                 num_kv_heads=cfg.get("num_key_value_heads", h),
                 d_ff=cfg["intermediate_size"],
                 vocab_size=cfg["vocab_size"],
                 head_dim=cfg.get("head_dim", d // h))
    if base.pos_emb == "learned":
        sizes["max_position"] = cfg["max_position_embeddings"]
    if base.pos_emb == "rope":
        sizes["rope_theta"] = float(cfg["rope_theta"])
    return dc.replace(base, **sizes)


def span(name: str):
    """A host span around one of the benchmark's own calls, written into
    the profiler's trace (about a microsecond when the profiler is off)."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)
