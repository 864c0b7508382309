"""On-demand XLA profiling — three triggers, one trace at a time.

- ``TPUDDP_PROFILE=<dir>`` (or ``1`` for ``<save_dir>/trace``): trace the
  FIRST epoch — the original env toggle, unchanged.
- ``TPUDDP_PROFILE_STEPS=<start>:<stop>``: trace the train-step window
  ``[start, stop)`` (global step index since loop entry). The trace starts
  before the dispatch that contains ``start`` and stops after the dispatch
  containing ``stop - 1`` completes on device — exact at ``scan_steps: 1``,
  rounded outward to whole fused groups otherwise (the window always
  *covers* the requested steps). Trace dir: the ``TPUDDP_PROFILE`` value
  when that names a directory, else ``<save_dir>/trace_steps_<start>_<stop>``.
- ``SIGUSR1``: capture ONE full epoch's trace from a live run — send the
  signal, the next epoch is traced into ``<save_dir>/trace_sigusr1_e<N>``.

jax.profiler supports one active trace, so all three funnel through the
module latch; a trigger that finds a trace already running is skipped with
a warning instead of crashing the run.

Whatever the trigger, the capture's device operations carry the program's own
names (:func:`scope`): the phase of the step each belongs to
(:data:`PHASE_SCOPES`) and, under ``tpuddp.forward``, the model layer it came
from (``3_Conv2d``, ``12_Bottleneck/conv2``).
"""

from __future__ import annotations

import logging
import os
import signal
import threading
from typing import Optional, Tuple

import jax

logger = logging.getLogger("tpuddp")

# -- the names device operations carry ---------------------------------------
#
# One vocabulary for the whole program. The step builders open a phase scope
# round each part of the step while it is traced; ``nn.Sequential`` and the
# residual blocks open one scope per child under ``tpuddp.forward``. XLA keeps
# the scope path as each operation's ``op_name``, which a profiler capture
# shows as ``tf_op``. The backward pass needs no scope of its own: JAX writes
# it as ``transpose(jvp(tpuddp.forward))/<layer path>/<primitive>``. The names
# are metadata only: the compiled program is the same with and without them
# (tests/test_device_scopes.py holds that).
SCOPE_PREFIX = "tpuddp."
AUGMENT = SCOPE_PREFIX + "augment"  # the on-device augment/resize of a batch
FORWARD = SCOPE_PREFIX + "forward"  # model.apply; layer scopes nest under it
LOSS = SCOPE_PREFIX + "loss"  # the criterion
BUFFERS = SCOPE_PREFIX + "buffers"  # sync_buffers' broadcast / pmean
EXCHANGE = SCOPE_PREFIX + "exchange"  # cross-replica gradient exchange
CLIP = SCOPE_PREFIX + "clip"  # clip-after-aggregate
GUARD = SCOPE_PREFIX + "guard"  # the firewall's verdict and its lax.cond
OPTIMIZER = SCOPE_PREFIX + "optimizer"  # optimizer.update
METRICS = SCOPE_PREFIX + "metrics"  # the step's metric sums
PHASE_SCOPES = (
    AUGMENT, FORWARD, LOSS, BUFFERS, EXCHANGE, CLIP, GUARD, OPTIMIZER, METRICS,
)
# Names are metadata, and JAX leaves metadata out of the persistent compile
# cache's key: a program cached under older names is served with them. So the
# cache lives in a subdirectory named for this version
# (utils/compile_cache.py). Change it when, and only when, a scope's name or
# its place in the step changes; the cost is one cold start per checkout.
NAMES_VERSION = "names-v1"


def scope(name: str):
    """Name the device operations traced inside the ``with`` block. Runs
    while a step is traced, never per step on the host."""
    return jax.named_scope(name)


def layer_scope(index: int, layer) -> str:
    """The scope ``nn.Sequential`` gives child ``index``: ``3_Conv2d``."""
    return f"{index}_{type(layer).__name__}"


_PROFILE_ENV = "TPUDDP_PROFILE"
_PROFILE_STEPS_ENV = "TPUDDP_PROFILE_STEPS"
_profiling = {"active": False}
_sigusr1 = {"installed": False, "requested": False}


def _start_trace(target: str) -> bool:
    if _profiling["active"]:
        logger.warning(
            "profiler trigger for %s skipped: a trace is already active", target
        )
        return False
    os.makedirs(target, exist_ok=True)
    jax.profiler.start_trace(target)
    _profiling["active"] = True
    return True


def maybe_start_profiler(default_dir: Optional[str] = None) -> bool:
    """Start an XLA trace if $TPUDDP_PROFILE is set (its value is the trace
    dir; '1' falls back to ``default_dir``/trace). Returns True if started.

    When $TPUDDP_PROFILE_STEPS is also set, the step window OWNS the trace
    and the first-epoch mode stands down (one trace at a time)."""
    target = os.environ.get(_PROFILE_ENV)
    if not target or _profiling["active"]:
        return False
    if os.environ.get(_PROFILE_STEPS_ENV):
        return False
    if target == "1":
        if default_dir is None:
            return False
        target = os.path.join(default_dir, "trace")
    return _start_trace(target)


def stop_profiler() -> None:
    if _profiling["active"]:
        jax.profiler.stop_trace()
        _profiling["active"] = False


def parse_profile_steps(
    raw: Optional[str] = None,
) -> Optional[Tuple[int, int]]:
    """``$TPUDDP_PROFILE_STEPS`` as ``(start, stop)``; None when unset.
    Malformed values are refused loudly — a typo'd window silently ignored
    would "profile" nothing and report success."""
    raw = os.environ.get(_PROFILE_STEPS_ENV, "") if raw is None else raw
    if not raw:
        return None
    try:
        start_s, stop_s = raw.split(":")
        start, stop = int(start_s), int(stop_s)
    except ValueError:
        raise ValueError(
            f"{_PROFILE_STEPS_ENV}={raw!r} is not <start>:<stop> "
            "(two integers, e.g. 100:110)"
        )
    if start < 0 or stop <= start:
        raise ValueError(
            f"{_PROFILE_STEPS_ENV}={raw!r}: need 0 <= start < stop"
        )
    return start, stop


class StepWindowProfiler:
    """The $TPUDDP_PROFILE_STEPS driver hook.

    The epoch driver calls :meth:`before_dispatch` with the global step index
    the upcoming dispatch starts at and how many fused steps it covers, and
    :meth:`after_dispatch` with the dispatch's output. Inert (two integer
    compares per dispatch) when the env knob is unset."""

    def __init__(self, save_dir: Optional[str]):
        self.window = parse_profile_steps()
        self.dir = None
        self.active = False
        self.done = self.window is None
        if self.window is not None:
            start, stop = self.window
            explicit = os.environ.get(_PROFILE_ENV)
            if explicit and explicit != "1":
                self.dir = explicit
            elif save_dir is not None:
                self.dir = os.path.join(
                    save_dir, f"trace_steps_{start}_{stop}"
                )
            else:
                logger.warning(
                    "%s set but no trace dir resolvable (no save_dir and no "
                    "%s=<dir>); step-window profiling disabled",
                    _PROFILE_STEPS_ENV,
                    _PROFILE_ENV,
                )
                self.done = True

    def before_dispatch(self, global_step: int, n_steps: int) -> None:
        if self.done or self.active:
            return
        start, _ = self.window
        if global_step + n_steps > start:  # this dispatch contains `start`
            self.active = _start_trace(self.dir)
            if not self.active:
                self.done = True  # trace slot taken; don't retry every step

    def after_dispatch(self, global_step_end: int, fence=None) -> None:
        if not self.active:
            return
        _, stop = self.window
        if global_step_end >= stop:
            if fence is not None:
                # the trace must contain the window's *execution*, not just
                # its dispatch: block on the last covered dispatch's output
                jax.block_until_ready(fence)
            stop_profiler()
            self.active = False
            self.done = True
            logger.info(
                "step-window trace [%d, %d) captured -> %s",
                self.window[0],
                stop,
                self.dir,
            )

    def finish(self, fence=None) -> None:
        """Loop teardown: a window that never reached ``stop`` (short run,
        exception) still flushes its partial trace — it is the post-mortem."""
        if self.active:
            self.after_dispatch(self.window[1], fence)
            if self.active:  # stop index never reached: force the flush
                stop_profiler()
                self.active = False
                self.done = True


# --------------------------------------------------------------- SIGUSR1 --


def _on_sigusr1(signum, frame) -> None:
    _sigusr1["requested"] = True


def install_sigusr1_trigger() -> bool:
    """Arm the SIGUSR1 -> trace-next-epoch trigger. Main-thread only (the
    Python signal limitation, same as the preemption handlers); returns False
    and stays a no-op elsewhere."""
    if _sigusr1["installed"]:
        return True
    if threading.current_thread() is not threading.main_thread():
        logger.debug("not main thread; SIGUSR1 profile trigger not installed")
        return False
    try:
        signal.signal(signal.SIGUSR1, _on_sigusr1)
    except (ValueError, OSError, AttributeError):  # exotic platforms
        return False
    _sigusr1["installed"] = True
    return True


def consume_sigusr1_request() -> bool:
    """True once per received SIGUSR1 (the epoch driver polls this at each
    epoch start and traces that epoch when it fires)."""
    if _sigusr1["requested"]:
        _sigusr1["requested"] = False
        return True
    return False


def start_epoch_trace(save_dir: Optional[str], epoch: int) -> bool:
    """Start the SIGUSR1-requested one-epoch trace."""
    if save_dir is None:
        logger.warning("SIGUSR1 trace requested but no save_dir; skipped")
        return False
    return _start_trace(os.path.join(save_dir, f"trace_sigusr1_e{epoch}"))


def reset_profiling_state() -> None:
    """Test isolation: drop the latch and any pending SIGUSR1 request."""
    if _profiling["active"]:
        stop_profiler()
    _sigusr1["requested"] = False
