"""Shared benchmark plumbing: CSV emission, fingerprinted result caching,
and the common ``BENCH_*.json`` envelope every benchmark emits through.

Importing this module also puts ``<repo>/src`` on ``sys.path`` (resolved
from this file, not the CWD), so every benchmark starts with
``import common`` and then imports ``repro.*`` directly -- no per-script
``sys.path.insert(0, "src")`` boilerplate that silently breaks when the
script is launched from anywhere but the repo root.

Importing it also points jax's persistent compilation cache at a fixed
directory (``repro.launch.compile_cache``), so repeated benchmark runs in
one tree reuse compiled programs.
"""

from __future__ import annotations

import datetime
import hashlib
import inspect
import json
import platform
import subprocess
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
_SRC = str(_ROOT / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache(_ROOT)

RESULTS = Path(__file__).resolve().parent / "results"

#: envelope schema version of every BENCH_*.json; bump on breaking changes
BENCH_SCHEMA = "rasa-bench/1"

#: envelope keys every BENCH file must carry (checked by validate_bench)
BENCH_KEYS = ("schema", "benchmark", "git_rev", "timestamp_utc", "backend",
              "host", "python", "data")


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}")


def _git_rev() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=Path(__file__).resolve().parent, capture_output=True,
            text=True, timeout=10)
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else "unknown"
    except OSError:
        return "unknown"


def bench_envelope(benchmark: str, backend: str | None = None) -> dict:
    """The shared metadata block of a ``BENCH_<benchmark>.json`` file.

    Makes the perf trajectory machine-comparable across PRs: which commit,
    when, on which host/interpreter, and on which simulation backend the
    numbers were produced.
    """
    return {
        "schema": BENCH_SCHEMA,
        "benchmark": benchmark,
        "git_rev": _git_rev(),
        "timestamp_utc": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "backend": backend,
        "host": platform.node() or "unknown",
        "python": platform.python_version(),
    }


def write_bench(benchmark: str, data, backend: str | None = None) -> Path:
    """Write ``BENCH_<benchmark>.json``: the shared envelope + ``data``."""
    RESULTS.mkdir(parents=True, exist_ok=True)
    path = RESULTS / f"BENCH_{benchmark}.json"
    payload = bench_envelope(benchmark, backend)
    payload["data"] = data
    path.write_text(json.dumps(payload, indent=2))
    return path


def validate_bench(path: Path) -> list[str]:
    """Schema-check one BENCH file; returns a list of problems (empty = ok).

    Checked: parseable JSON object, every envelope key present, schema
    version match, and the embedded benchmark name agreeing with the
    ``BENCH_<name>.json`` filename.
    """
    path = Path(path)
    try:
        doc = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path.name}: unreadable ({e})"]
    if not isinstance(doc, dict):
        return [f"{path.name}: top level must be an object, "
                f"got {type(doc).__name__}"]
    errors = [f"{path.name}: missing envelope key {k!r}"
              for k in BENCH_KEYS if k not in doc]
    if doc.get("schema") not in (None, BENCH_SCHEMA):
        errors.append(f"{path.name}: schema {doc['schema']!r} != "
                      f"{BENCH_SCHEMA!r}")
    expect = path.stem.removeprefix("BENCH_")
    if "benchmark" in doc and doc["benchmark"] != expect:
        errors.append(f"{path.name}: benchmark {doc['benchmark']!r} does "
                      f"not match filename ({expect!r})")
    return errors


def model_fingerprint(*sources) -> str:
    """Content hash of the model code a benchmark's numbers depend on.

    ``sources`` are modules (hashed by source file) or path strings.  Pass
    the result as ``cache_json(..., fingerprint=...)`` so that editing the
    simulator invalidates cached benchmark results instead of silently
    serving stale numbers.
    """
    h = hashlib.sha256()
    for src in sources:
        path = Path(src) if isinstance(src, (str, Path)) else \
            Path(inspect.getsourcefile(src))
        h.update(path.name.encode())
        h.update(path.read_bytes())
    return h.hexdigest()[:16]


def device_key() -> str:
    """``<platform>/<device_kind>`` of the device jax computes on."""
    import jax
    d = jax.devices()[0]
    return f"{d.platform}/{d.device_kind}"


def cache_json(key: str, fn, force: bool = False,
               fingerprint: str | None = None):
    """Return the cached result for ``key``, or compute and cache ``fn()``.

    The cache file records the device (:func:`device_key`) and
    ``fingerprint``; a cached result is served only when both match, so a
    result computed on one platform is never served on another and editing
    the model code invalidates it.  Anything else (legacy files included)
    is recomputed.  ``force=True`` always recomputes.
    """
    RESULTS.mkdir(parents=True, exist_ok=True)
    p = RESULTS / f"{key}.json"
    stamp = {"__device__": device_key(), "__fingerprint__": fingerprint}
    if p.exists() and not force:
        cached = json.loads(p.read_text())
        if isinstance(cached, dict) and "data" in cached and all(
                cached.get(k) == v for k, v in stamp.items()):
            return cached["data"]
    out = fn()
    p.write_text(json.dumps({**stamp, "data": out}, indent=2))
    return out


def timeit(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median wall-time of fn(*args) in microseconds."""
    for _ in range(warmup):
        fn(*args)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6
