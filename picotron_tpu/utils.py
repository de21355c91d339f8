"""Shared utilities: seeds, formatting, MFU accounting, rank-aware printing.

Capability parity with the reference's utils (ref: picotron/utils.py), with the
hardware constants made TPU-native: the reference hardcodes the H100 bf16 peak
(989.5 TFLOP/s, ref: utils.py:42); here peak FLOP/s is looked up per TPU
generation from the device kind, as SURVEY.md §5 prescribes.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from typing import Optional

import jax

from picotron_tpu.config import Config, ModelConfig, num_params


# ---------------------------------------------------------------------------
# Hardware peaks
# ---------------------------------------------------------------------------

# Published per-chip bf16 peak FLOP/s by TPU generation.
TPU_PEAK_FLOPS: dict[str, float] = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,  # Trillium
    "v6p": 918e12,
}
# The reference's H100 constant, kept for apples-to-apples MFU comparison
# against its published numbers (ref: utils.py:42).
H100_BF16_PEAK = 989.5e12


def tpu_generation(device_kind: str) -> str:
    """TPU generation ('v5e', 'v5p', ...) from a jax `device_kind`.

    Real device_kind strings use the hardware naming, not the marketing one:
    a v5e reports "TPU v5 lite", a v6e/Trillium "TPU v6 lite", a v5p
    "TPU v5p" (and "TPU v5" alone means v5p). A kind that names no known
    generation (the CPU platform, a future chip) raises: a peak or a link
    bandwidth borrowed from another device would make every derived
    utilization wrong without saying so."""
    kind = device_kind.lower()
    if "v6" in kind or "trillium" in kind:
        return "v6e"
    if "v5 lite" in kind or "v5lite" in kind or "v5e" in kind:
        return "v5e"
    if "v5" in kind:  # "TPU v5p" / bare "TPU v5"
        return "v5p"
    for gen in ("v4", "v3", "v2"):
        if gen in kind:
            return gen
    raise ValueError(
        f"device_kind {device_kind!r} is not a TPU generation this repo "
        f"has constants for ({', '.join(TPU_PEAK_FLOPS)}); add it to the "
        f"peak table with its source before computing utilization on it")


def device_peak_flops(device: Optional[jax.Device] = None) -> float:
    """Per-chip bf16 peak FLOP/s for `device` (default: first local device);
    raises ValueError on a device_kind outside the peak table."""
    if device is None:
        device = jax.devices()[0]
    return TPU_PEAK_FLOPS[tpu_generation(device.device_kind)]


# ---------------------------------------------------------------------------
# Process set-up: where the program runs, where compiled code is kept
# ---------------------------------------------------------------------------

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at a stable directory and
    return it. Called by every entry point that compiles on the chip
    (train, bench, chip_smoke, `pytest -m tpu`) before its first compile.

    `JAX_COMPILATION_CACHE_DIR`, when set, is where the cache lives — JAX
    reads the variable itself and this function sets nothing. Otherwise
    the cache goes to `<checkout>/.jax_cache`: a cache that moves never
    hits, so the path is fixed (never a temp name, a pid or a time), and
    child processes resolve the same one. A process pinned to the CPU
    platform (tests, tools) gets no cache and None: its programs are toys,
    and a test run must not grow the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if jax.config.jax_platforms == "cpu":
        return None
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_platform(who: str, *, allow_cpu: bool) -> jax.Device:
    """Initialize the backend and return its first device, refusing the one
    outcome nobody asked for: JAX found no accelerator and quietly fell
    back to the CPU. `allow_cpu` is the caller's explicit consent (a
    `--cpu` flag, `use_cpu: true`, or — for the trainer — a
    `JAX_PLATFORMS=cpu` the user set). One line, no traceback."""
    try:
        dev = jax.devices()[0]
    except Exception as e:  # noqa: BLE001 — entry-point boundary: whatever
        # the backend raised, the user gets the one-line story
        why = (str(e).splitlines() or [""])[0][:200]
        raise SystemExit(
            f"{who}: no JAX backend came up "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')}; "
            f"{type(e).__name__}: {why})")
    if dev.platform == "cpu" and not allow_cpu:
        raise SystemExit(
            f"{who}: no accelerator — JAX is running on the CPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')}), "
            f"which this run did not ask for")
    return dev


# ---------------------------------------------------------------------------
# FLOPs / MFU accounting (ref: utils.py:39-48)
# ---------------------------------------------------------------------------


def flops_per_token(m: ModelConfig, seq_length: int) -> float:
    """Training FLOPs per token: 6N + 12·L·h·s — same formula the reference
    uses so MFU numbers are directly comparable (ref: utils.py:46-47).
    """
    # MoE: only visited experts compute; tied head: the matmul runs anyway
    n = num_params(m, active_only=True, include_tied_head=True)
    return 6.0 * n + 12.0 * m.num_hidden_layers * m.hidden_size * seq_length


def mfu(tokens_per_second: float, m: ModelConfig, seq_length: int,
        num_chips: int, peak_flops_per_chip: Optional[float] = None) -> float:
    """Model FLOPs utilization in [0, 1]."""
    if peak_flops_per_chip is None:
        peak_flops_per_chip = device_peak_flops()
    achieved = tokens_per_second * flops_per_token(m, seq_length)
    return achieved / (peak_flops_per_chip * num_chips)


# ---------------------------------------------------------------------------
# Formatting / logging (ref: utils.py:12-37)
# ---------------------------------------------------------------------------


def human_format(num: float) -> str:
    """1234567 -> '1.23M' (ref: utils.py:27-37)."""
    num = float(f"{num:.3g}")
    magnitude = 0
    while abs(num) >= 1000:
        magnitude += 1
        num /= 1000.0
    suffix = ["", "K", "M", "B", "T", "P"][magnitude]
    return f"{num:f}".rstrip("0").rstrip(".") + suffix


def is_logging_host() -> bool:
    """Single-controller analogue of the reference's wandb-rank gate
    (ref: train.py:101): under JAX only process 0 logs."""
    return jax.process_index() == 0


def log_print(*args, **kwargs) -> None:
    """Print from the logging host only (the reference needs an fcntl file
    lock to serialize per-rank prints, ref: utils.py:12-20; a single
    controller per host makes that a process_index gate)."""
    if is_logging_host():
        print(*args, **kwargs)
        sys.stdout.flush()


class StepTimer:
    """Wall-clock per-step timing for tokens/s (ref: train.py:220,242)."""

    def __init__(self) -> None:
        self._t0 = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt = now - self._t0
        self._t0 = now
        return dt


def training_log_line(step: int, loss: float, tokens_per_sec: float,
                      tokens_per_sec_per_chip: float, mfu_frac: float,
                      trained_tokens: int, memory_gb: float = 0.0,
                      extras: Optional[dict] = None) -> str:
    """The per-step console line. Format is a de-facto API consumed by the
    metrics harvester (ref: train.py:248-259 <-> extract_metrics.py:55-68);
    tools/extract_metrics.py parses exactly these field names. `extras`
    appends step-metric scalars after the stable fields (e.g. MoE's
    `moe_drop_frac`), so the harvester's prefix parse is unaffected."""
    line = (
        f"[step {step:06d}] loss: {loss:.4f} | "
        f"tokens/s: {human_format(tokens_per_sec)} | "
        f"tokens/s/chip: {human_format(tokens_per_sec_per_chip)} | "
        f"MFU: {100.0 * mfu_frac:.2f}% | "
        f"tokens: {human_format(trained_tokens)} | "
        f"mem: {memory_gb:.1f}GB"
    )
    for k, v in (extras or {}).items():
        line += f" | {k}: {v:.4f}"
    return line


def dump_all_stacks(file=None) -> None:
    """Write every thread's Python stack to `file` (default stderr) — the
    watchdog's post-mortem when a step or the data producer hangs: which
    thread is stuck, and where. Thread names come from threading;
    sys._current_frames also surfaces threads the module does not know."""
    file = file or sys.stderr
    names = {t.ident: t.name for t in threading.enumerate()}
    for ident, frame in sys._current_frames().items():
        print(f"--- thread {names.get(ident, '<unknown>')} "
              f"(ident {ident}) ---", file=file)
        traceback.print_stack(frame, file=file)
    file.flush()


def device_memory_gb() -> float:
    """Peak on-device memory in GiB if the backend exposes it (the TPU
    analogue of torch.cuda.memory_reserved, ref: train.py:255). Max over
    this process's local devices — under tp/pp sharding different chips
    peak differently, and the max is the one that OOMs. (Cross-host maxing
    would need a collective; each host logging its own max is the useful
    view since log_print gates to process 0, whose chips are
    representative under SPMD.)"""
    peak = 0.0
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
            if stats and "peak_bytes_in_use" in stats:
                peak = max(peak, stats["peak_bytes_in_use"] / (1024 ** 3))
        except Exception:
            pass
    return peak
