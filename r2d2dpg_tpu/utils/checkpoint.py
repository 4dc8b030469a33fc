"""Checkpoint / resume via orbax (SURVEY.md §5.4).

Reference parity: the reference at most does periodic
``torch.save(state_dict)`` with no optimizer/replay state and no resume path
(SURVEY §5.4).  The build checkpoints the **entire** ``TrainerState`` pytree —
params, optimizer states, target nets, RNG, replay arena (data + priorities +
cursor), env state, episode accumulators — so a restore resumes the run
exactly (for pure-JAX envs) or near-exactly (host-backed envs; see below).

Host-backed envs (``dmc_host``): MuJoCo physics lives on the host, outside
the pytree, so it cannot be checkpointed through this path.  On restore the
env portion of the state is re-initialized (fresh episodes, zeroed carries);
replay, learner and counters resume intact.  The first ``seq_len`` post-resume
steps re-fill the window before sequences are emitted again, exactly like the
initial warm-up — no corrupt sequences enter replay.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp


class CheckpointManager:
    """Periodic save + latest-restore of ``TrainerState`` under ``directory``.

    A thin wrapper over ``orbax.checkpoint.CheckpointManager`` that knows how
    to rebuild the abstract pytree template from a ``Trainer`` and to patch
    up host-backed env state on restore.
    """

    def __init__(
        self,
        directory: str,
        *,
        save_every: int = 500,
        max_to_keep: int = 3,
        async_save: bool = False,
        light: bool = False,
    ):
        # ``light``: save only the learner subtree ({"train": state.train} —
        # params, targets, optimizer states, step) instead of the full
        # TrainerState.  MBs instead of GBs (no replay arena / window /
        # env fleet), so periodic saves are affordable mid-measurement,
        # and the on-disk layout is exactly what eval.py restores.  Resume
        # from a light checkpoint continues learning with a FRESH replay
        # and phase schedule (warm-up/fill re-run) — by design.
        self.light = light
        # orbax rejects relative paths at SAVE time (deep inside the first
        # cadence hit — a run can train for minutes and then die); absolutize
        # up front so `--checkpoint-dir runs/x/ckpt` just works.
        self.directory = os.path.abspath(directory)
        self.save_every = save_every
        # Synchronous by default (VERDICT r1 weak #3): orbax's async save
        # finalizes on a background thread, which a busy single-core host
        # starves — the one long round-1 run left ONLY un-finalized
        # ``*.orbax-checkpoint-tmp`` dirs and ``--resume`` found nothing.
        # A blocking save is a few seconds every ``save_every`` phases and
        # is durable the moment it returns.
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                create=True,
                enable_async_checkpointing=async_save,
            ),
        )

    # ------------------------------------------------------------------ save
    # ``save_every`` semantics: N>0 = every N phases (+ the caller's final
    # save); -1 = final-save-only (maybe_save never fires, but the truthy
    # value keeps train.py's finally-block save armed); 0 = off entirely.
    def maybe_save(self, phase: int, state: Any) -> bool:
        """Save if ``phase`` hits the cadence.  Returns True when saved."""
        if self.save_every <= 0 or phase % self.save_every != 0:
            return False
        self.save(phase, state)
        return True

    def save(self, step: int, state: Any) -> None:
        """Save at ``step``, overwriting an existing same-step checkpoint
        (a light-resume run restarts its phase numbering at 0, so a
        resumed run legitimately revisits steps already on disk)."""
        from r2d2dpg_tpu.obs import flight_event

        self._check_layout(saving=True)
        if step in (self._mgr.all_steps() or []):
            self._mgr.delete(step)
        payload = {"train": state.train} if self.light else state
        self._mgr.save(step, args=ocp.args.StandardSave(payload))
        # The flight recorder's checkpoint trail is what the divergence
        # watchdog's "last-good checkpoint" pointer reads at abort time.
        flight_event(
            "checkpoint_save",
            step=int(step),
            directory=self.directory,
            light=self.light,
        )

    def save_final(self, step: int, state: Any) -> None:
        """End-of-run save; no-op when the cadence already saved ``step``
        (orbax raises StepAlreadyExistsError otherwise, which would turn a
        successful run into a failed one at teardown)."""
        if self._mgr.latest_step() == step:
            return
        self.save(step, state)

    _LAYOUT_MARKER = "LIGHT_CHECKPOINTS"

    def _check_layout(self, *, saving: bool) -> None:
        """Refuse light/full mode mismatches against what's on disk, with a
        clear message instead of an opaque orbax tree-structure error."""
        marker = os.path.join(self.directory, self._LAYOUT_MARKER)
        has_steps = bool(self._mgr.all_steps())
        if self.light:
            if has_steps and not os.path.exists(marker):
                raise ValueError(
                    f"{self.directory} holds FULL checkpoints but this "
                    "manager is light=True — drop --checkpoint-light or "
                    "point at a fresh directory"
                )
            if saving and not os.path.exists(marker):
                with open(marker, "w") as f:
                    f.write("train-subtree-only checkpoints\n")
        elif os.path.exists(marker):
            raise ValueError(
                f"{self.directory} holds LIGHT checkpoints but this "
                "manager is light=False — pass --checkpoint-light to match "
                "(eval.py is unaffected: it restores the train subtree "
                "from either layout)"
            )

    def wait(self) -> None:
        """Block until async saves are durable (call before process exit)."""
        self._mgr.wait_until_finished()

    # --------------------------------------------------------------- restore
    @property
    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> list:
        """Steps currently on disk (post ``max_to_keep`` pruning) — the
        fleet counter-sidecar pruning keys off this (fleet/ingest.py)."""
        return list(self._mgr.all_steps() or [])

    def restore(self, template: Any) -> Any:
        """Restore the latest checkpoint into the structure of ``template``.

        ``template`` is a concrete ``TrainerState`` (e.g. ``trainer.init()``)
        — its shapes/dtypes/shardings define the restore target, so restored
        arrays land with the same mesh layout the trainer expects.  In
        ``light`` mode only the learner subtree is stored, so the template
        is narrowed to it and the result is the restored ``train`` subtree.
        """
        self._check_layout(saving=False)
        step = self._mgr.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint found under {self.directory}"
            )
        target = {"train": template.train} if self.light else template
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                jnp.shape(x), x.dtype, sharding=getattr(x, "sharding", None)
            )
            if isinstance(x, (jax.Array, np.ndarray))
            else x,
            target,
        )
        try:
            out = self._mgr.restore(step, args=ocp.args.StandardRestore(abstract))
        except ValueError as refused:
            # orbax reads no leaf into another shape.  A replay saved in an
            # older storage shape is read as saved and reshaped; whatever
            # else was refused stays refused.
            with ocp.StandardCheckpointer() as reader:
                saved = reader.metadata(
                    os.path.join(self.directory, str(step), "default")
                ).item_metadata
            as_saved = _replay_as_saved(abstract, saved)
            if as_saved is None:
                raise refused
            out = _replay_as_stored(
                self._mgr.restore(step, args=ocp.args.StandardRestore(as_saved)),
                abstract,
            )
        return out["train"] if self.light else out

    def close(self) -> None:
        self._mgr.close()


def _names(path) -> tuple:
    """A tree path as the names on it, whatever kind of node each names
    (the checkpoint's metadata is dicts all the way down, the state is
    dataclasses)."""
    return tuple(
        str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
        for k in path
    )


def _replay_as_saved(abstract: Any, metadata: Any) -> Any:
    """``abstract`` (the restore target) with every replay field in the shape
    the checkpoint holds it in; ``None`` where none has another.

    The arena stores a large row as whole tiles since PR 34, and a small row
    of 128 elements or more as its whole lane-rows and the rest since PR 40,
    either as a ``StoredRows`` of its parts (``replay/arena.py::
    _storage_parts``: a pixel field ``[capacity, L, 3, 32, 128]``, walker's
    observations ``[capacity, 1024]`` and ``[capacity, 8]``).  A checkpoint
    written before holds such a field as one leaf in the rows' own shape
    (``[capacity, L, 64, 64, 3]``, ``[capacity, 43, 24]``) or, in between, as
    tiles, which orbax refuses to read into another shape or tree.  Same
    dtype, same slots, same elements in the same order: such a leaf is read
    as saved and ``_replay_as_stored`` stores its rows.  A replay field that
    differs in anything else is refused by name."""
    from r2d2dpg_tpu.replay.arena import StoredRows

    saved = {
        _names(path): m
        for path, m in jax.tree_util.tree_flatten_with_path(metadata)[0]
    }
    older = []

    def fit(path, want):
        names = _names(path)
        m = saved.get(names)
        parts = isinstance(want, StoredRows)
        if (
            not ("arena" in names and "data" in names)
            or m is None
            or (not parts and tuple(m.shape) == tuple(want.shape))
        ):
            return want
        first = want.parts[0] if parts else want
        row = want.row if parts else tuple(want.shape[1:])
        if (
            m.dtype != first.dtype
            or tuple(m.shape)[:1] != tuple(first.shape)[:1]
            or math.prod(m.shape[1:]) != math.prod(row)
        ):
            raise ValueError(
                f"checkpoint replay leaf {jax.tree_util.keystr(path)} is "
                f"{m.dtype}{list(m.shape)}; this arena stores {first.dtype}"
                f"{[first.shape[0], *row]}: another capacity, row or dtype, not "
                "an older storage shape of the same rows"
            )
        older.append(names)
        return jax.ShapeDtypeStruct(
            tuple(m.shape), first.dtype, sharding=first.sharding
        )

    as_saved = jax.tree_util.tree_map_with_path(
        fit, abstract, is_leaf=lambda x: isinstance(x, StoredRows)
    )
    return as_saved if older else None


def _replay_as_stored(restored: Any, abstract: Any) -> Any:
    """``restored`` with the leaves ``_replay_as_saved`` read in an older
    storage shape stored as the arena stores them (``abstract``'s shapes),
    on its sharding."""
    from r2d2dpg_tpu.replay.arena import StoredRows, _as_stored

    def put(x, want):
        return x if want.sharding is None else jax.device_put(x, want.sharding)

    def fit(got, want):
        if isinstance(got, StoredRows) or not isinstance(
            want, (StoredRows, jax.ShapeDtypeStruct)
        ):
            return got
        if not isinstance(want, StoredRows) and got.shape == want.shape:
            return got
        return jax.tree_util.tree_map(put, _as_stored(want, got), want)

    return jax.tree_util.tree_map(
        fit, restored, abstract, is_leaf=lambda x: isinstance(x, StoredRows)
    )


def check_restored_leaves(restored: Any, template: Any, *, where: str, hint: str) -> None:
    """Strict leaf-for-leaf validation of an orbax restore (VERDICT r4 weak
    #2c, shared by eval and serving hot-reload).

    Two silent orbax behaviors must fail LOUDLY here, not as an opaque
    TypeError later inside a jitted consumer:

    * missing checkpoint key -> the template leaf comes back UNRESTORED
      (still an abstract ``ShapeDtypeStruct``);
    * shape/dtype mismatch -> orbax ignores the template and hands back the
      CHECKPOINT's array (verified against orbax in-tree: a [2,H]
      twin-critic template restores a [H] single-critic checkpoint leaf
      without complaint).
    """
    missing, mismatched = [], []
    for (path, got), want in zip(
        jax.tree_util.tree_leaves_with_path(restored),
        jax.tree_util.tree_leaves(template),
    ):
        if isinstance(got, jax.ShapeDtypeStruct):
            missing.append(jax.tree_util.keystr(path))
        elif got.shape != want.shape or got.dtype != want.dtype:
            mismatched.append(
                f"{jax.tree_util.keystr(path)} (checkpoint "
                f"{got.dtype}{list(got.shape)} vs expected "
                f"{want.dtype}{list(want.shape)})"
            )
    if not (missing or mismatched):
        return

    def _clip(items):
        return ", ".join(items[:8]) + (" ..." if len(items) > 8 else "")

    raise ValueError(
        f"checkpoint at {where} does not match the restore template "
        f"({hint}): "
        + (f"{len(missing)} leaves missing: {_clip(missing)}; "
           if missing else "")
        + (f"{len(mismatched)} leaves mismatched: {_clip(mismatched)}"
           if mismatched else "")
    )


def abstract_template(tree: Any, *, sharding=None) -> Any:
    """Map a (concrete or ``eval_shape``) pytree to ``ShapeDtypeStruct``
    leaves with an explicit sharding — orbax warns that a restore without
    sharding info is unsafe across topologies (ADVICE r1)."""
    if sharding is None:
        sharding = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(jnp.shape(l), l.dtype, sharding=sharding),
        tree,
    )


def restore_subtree(
    checkpoint_dir: str, item: Any, *, step: Optional[int] = None
) -> tuple:
    """Partial-restore ``item`` (an abstract-template tree keyed like the
    checkpoint, e.g. ``{"train": {"actor_params": tmpl}}``) from the latest
    (or given) step under ``checkpoint_dir``.  Returns ``(restored, step)``.

    Skipped keys are never read from disk, so the (potentially GBs of)
    replay arena costs nothing — this is what lets eval and the serving
    hot-reloader poll a live training run's dir cheaply.
    """
    # orbax rejects relative paths (CheckpointManager.__init__ does the same).
    mgr = ocp.CheckpointManager(os.path.abspath(checkpoint_dir))
    try:
        if step is None:
            step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint found under {checkpoint_dir}"
            )
        restore_args = ocp.args.PyTreeRestore(item, partial_restore=True)
        return mgr.restore(step, args=restore_args), step
    finally:
        mgr.close()


def resume_state(trainer, ckpt: CheckpointManager):
    """``trainer.init()`` overwritten by the latest checkpoint, env-corrected.

    For pure-JAX envs the restored state is returned as-is (bit-exact resume).
    For host-backed (``batched``) envs the host physics is gone, so the env
    slice of the state — env_state/obs/reset/carries/noise/episode_return and
    the assembler window — is taken fresh from ``trainer.init()`` while
    learner/replay/counters come from the checkpoint.

    Light checkpoints carry only the learner subtree: everything else
    (replay, window, env fleet, phase schedule) starts fresh and the
    warm-up/fill phases re-run — learning continues, experience restarts.
    """
    fresh = trainer.init()
    if ckpt.light:
        return dataclasses.replace(fresh, train=ckpt.restore(fresh))
    restored = ckpt.restore(fresh)
    if not getattr(trainer.env, "batched", False):
        return restored
    state = dataclasses.replace(
        restored,
        env_state=fresh.env_state,
        obs=fresh.obs,
        reset=fresh.reset,
        actor_carry=fresh.actor_carry,
        critic_carry=fresh.critic_carry,
        noise_state=fresh.noise_state,
        window=fresh.window,
        episode_return=fresh.episode_return,
    )
    # The zeroed window must re-fill with real steps before any sequence is
    # emitted, or zero-padded garbage would enter replay on the first
    # train_phase (which emits unconditionally).  collect_phase steps the
    # envs without emitting — exactly the initial warm-up, replayed here.
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    return state
