"""Checkpointing: sharded train-state save/resume + HF safetensors import.

Capability parity with the reference's checkpoint layer
(ref: picotron/checkpoint.py), upgraded where the TPU stack makes it free:

- **Training state** — the reference writes one `.pth` per (tp_rank, pp_rank)
  with the topology baked into the filename, saved only by dp/cp rank 0, and
  resume asserts the identical parallel layout (ref: checkpoint.py:242-278).
  Here Orbax saves the global arrays once (each host writes its shards), and
  restore takes the *target* sharding — resuming on a different
  DPxPPxCPxTP layout reshards automatically, the "easy win over the
  reference" SURVEY.md §5 calls out. Saved payload matches the reference's:
  model + optimizer + step + trained tokens (ref: checkpoint.py:254-259).
- **HF weight import** — the reference reads only this rank's tensors from
  (sharded or single-file) safetensors, TP-slices them, regex-renames
  safetensors->picotron names, then *discards the values* by re-running
  random init; weights are shape templates only (ref: checkpoint.py:93-101).
  Here `load_hf_safetensors` actually materializes the weights into the
  stacked param pytree (renaming + torch->jax layout transposes), because
  a real framework should fine-tune; `init_params` remains the random
  bootstrap path. Untied lm_head force-creation (ref: checkpoint.py:88-91)
  maps to falling back to the embedding matrix when the file has no
  `lm_head.weight`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from picotron_tpu.ckpt_integrity import (
    VerifyResult, atomic_write_text, build_manifest, retention_plan,
    rmtree, verify_step_dir, write_manifest,
)
from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.resilience import chaos, elastic
from picotron_tpu.resilience.retry import RetryPolicy, retry_call
from picotron_tpu.telemetry import bus as telemetry_bus
from picotron_tpu.train_step import TrainState


def _isdir(path: str) -> bool:
    """Directory probe through epath (Orbax's own path layer) so
    URL-style stores (gs://) answer correctly — os.path.isdir is always
    False on URL paths, which would classify every remote checkpoint as
    not-durable and silently disable auto-resume (code review r5)."""
    try:
        from etils import epath

        return epath.Path(path).is_dir()
    except ImportError:
        return os.path.isdir(path)


def _listdir(path: str) -> list:
    """Child names of a directory, [] when absent — epath-first for the
    same URL-store reason as _isdir."""
    try:
        from etils import epath

        root = epath.Path(path)
        return [p.name for p in root.iterdir()] if root.is_dir() else []
    except ImportError:
        return os.listdir(path) if os.path.isdir(path) else []


# ---------------------------------------------------------------------------
# Orbax-backed training-state checkpointing
# ---------------------------------------------------------------------------


class CheckpointManager:
    """Save/restore TrainState under `<save_dir>/step_<n>/` (ref:
    checkpoint.py:232-278; the per-(tp,pp)-rank filename scheme collapses to
    one logical global checkpoint).

    Lineage integrity (picotron_tpu/ckpt_integrity): every save ends with a
    commit manifest — per-file content digests of the committed step dir,
    written tmp+rename as the last act, hashed AFTER the async array write
    lands so the step path never waits on it. Restore-side, durability
    (Orbax finalization) is necessary but no longer sufficient:
    `latest_valid_step` walks the lineage newest-first and returns the
    newest step that is durable AND verifies against its manifest, so a
    bit-flipped shard or torn meta.json on the newest step costs a
    fallback (emitting a `ckpt_corrupt` event), not the run. Retention GC
    (`checkpoint.keep_last` / `keep_every`) prunes after each commit,
    never the last verified step.

    Multihost requirement: `save_dir` must be a filesystem shared by every
    host (GCS / NFS — the standard Cloud TPU arrangement, and what Orbax
    itself needs to assemble the sharded array write). meta.json and the
    manifest are written by process 0 and read by all processes on
    restore, which assumes the same shared view."""

    def __init__(self, cfg: Config, menv=None, directory: Optional[str] = None):
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.cfg = cfg
        self.menv = menv
        self.directory = os.path.abspath(directory or cfg.checkpoint.save_dir)
        # Post-write commit work (manifest hash + write, chaos hook, GC)
        # runs on this thread for async saves; joined by
        # wait_until_finished so durability still means "manifest too".
        self._commit_thread: Optional[threading.Thread] = None
        # Async by default (SURVEY §5 names async Orbax the TPU-native
        # upgrade over the reference's blocking .pth writes, ref:
        # checkpoint.py:246-260): save() returns once the device->host
        # copies are staged — safe even with donated step buffers, since
        # the staging happens before save() returns — and the disk write
        # proceeds concurrently with the next training steps.
        if cfg.checkpoint.async_save:
            self._ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        else:
            self._ckptr = ocp.StandardCheckpointer()
        # Flaky-store retry policy (resilience config): save/restore and
        # the durability probe all ride it. The probe variant keeps the
        # attempt budget but caps the delays — latest_step() probes every
        # step dir, and a 30 s backoff per dir would stall resume.
        self._retry = RetryPolicy.from_config(cfg.resilience)
        self._probe_retry = dataclasses.replace(
            self._retry,
            base_delay=min(self._retry.base_delay, 0.2),
            max_delay=min(self._retry.max_delay, 1.0))

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:08d}")

    def save(self, state: TrainState, trained_tokens: int = 0,
             dataloader_state: Optional[dict] = None) -> str:
        # At most one save in flight: a still-running previous write must
        # finish before its directory layout is mutated again.
        self._ckptr.wait_until_finished()
        step = int(state.step)
        path = self._step_dir(step)

        def _write():
            # Chaos injection + retry sit around the whole write so a
            # transient store failure (or an injected one) costs a
            # backoff, not the run; force=True makes the re-save of a
            # partially staged attempt idempotent.
            chaos.fire("ckpt_save", step=step)
            self._ckptr.save(
                os.path.join(path, "state"),
                {"params": state.params, "opt_state": state.opt_state,
                 "step": state.step},
                force=True,
            )
            if not self.cfg.checkpoint.async_save:
                self._ckptr.wait_until_finished()
            if jax.process_index() == 0:
                # Orbax coordinates the sharded array write across hosts;
                # the sidecar metadata must be written once, not per-host.
                # Written immediately (even mid-async-write): durability
                # is judged by the finalized `state` dir (latest_step),
                # not by meta.json. tmp+rename so a crash mid-write leaves
                # no torn JSON under the final name to poison restore.
                meta = {
                    "step": step,
                    "trained_tokens": int(trained_tokens),
                    "config": self.cfg.to_json_dict(),
                }
                if dataloader_state is not None:
                    meta["dataloader"] = dataloader_state
                atomic_write_text(os.path.join(path, "meta.json"),
                                  json.dumps(meta, indent=2))

        retry_call(_write, policy=self._retry,
                   describe=f"checkpoint save (step {step})")
        if self.cfg.checkpoint.async_save:
            # The manifest hashes the step dir's committed bytes, so it
            # must run after the async array write lands — on its own
            # thread, off the step path (the whole point of async saves).
            self._commit_thread = threading.Thread(
                target=self._commit, args=(step, path),
                name=f"ckpt-commit-{step}", daemon=False)
            self._commit_thread.start()
        else:
            self._commit(step, path)
        return path

    def _topology(self) -> dict:
        d = self.cfg.distributed
        return {"dp": d.dp_size, "pp": d.pp_size, "ep": d.ep_size,
                "cp": d.cp_size, "tp": d.tp_size,
                "world_size": d.world_size, "slices": d.slices,
                "process_count": jax.process_count()}

    def _commit(self, step: int, path: str) -> None:
        """Last act of a save: wait for the array write to land, then
        write the commit manifest (process 0; the write itself is
        tmp+rename-atomic) and run retention GC. A failure here leaves the
        checkpoint durable-but-legacy (still restorable, never ranked
        "verified") rather than failing the run — reported via the probe
        event, not an exception on the commit thread."""
        try:
            self._ckptr.wait_until_finished()
            if jax.process_index() == 0:
                def _hash_and_write():
                    manifest = build_manifest(
                        path, step=step, topology=self._topology())
                    write_manifest(path, manifest)
                    return manifest

                manifest = retry_call(
                    _hash_and_write, policy=self._probe_retry,
                    describe=f"manifest commit (step {step})")
                telemetry_bus.emit(
                    "ckpt_commit", step=step,
                    files=manifest["file_count"],
                    bytes=manifest["total_bytes"])
                # Corruption chaos mutates the *committed* bytes — the
                # fault the manifest machinery exists to catch.
                chaos.fire("ckpt_committed", step=step, path=path)
                self.gc()
        except Exception as e:  # noqa: BLE001
            self._probe_failed(path, e, what="manifest commit")

    def wait_until_finished(self) -> None:
        """Block until any in-flight async save is durable on disk AND its
        commit manifest is written. Call before process exit (train.py
        does) and before restoring a checkpoint this manager may still be
        writing."""
        self._ckptr.wait_until_finished()
        t = self._commit_thread
        if t is not None and t is not threading.current_thread():
            t.join()
            self._commit_thread = None

    def _is_durable(self, step_dirname: str) -> bool:
        """True when the step's `state` checkpoint is fully committed.
        Orbax's own finalization check covers both commit strategies —
        tmp-dir-plus-atomic-rename on posix and in-place-write-plus-commit-
        marker on GCS-style stores (where the final directory exists while
        the write is still in flight, so a bare isdir test would hand
        restore a torn checkpoint; code review r3)."""
        state_dir = os.path.join(self.directory, step_dirname, "state")
        if not _isdir(state_dir):
            return False
        try:
            # The probe itself retries transient store errors (short
            # backoff) — the general form of the old one-shot
            # _probe_failed: a 2-second GCS blip while listing steps must
            # not hide a durable checkpoint from auto_resume.
            return bool(retry_call(
                self._ocp.utils.is_checkpoint_finalized, state_dir,
                policy=self._probe_retry,
                describe=f"durability probe {step_dirname}"))
        except ValueError as e:
            # "not an Orbax-managed checkpoint path" (older Orbax APIs).
            # json.JSONDecodeError subclasses ValueError, so a torn
            # finalization-metadata file must NOT ride this branch to
            # "durable" (ADVICE r4) — it falls through to the not-durable
            # handler. The durable=True conclusion holds only for LOCAL
            # paths, where Orbax commits by atomic rename (the final
            # `state` dir existing at all means the rename happened);
            # URL-style stores commit via marker files, so absent metadata
            # there means possibly-torn, not durable.
            if isinstance(e, json.JSONDecodeError) or "://" in state_dir:
                return self._probe_failed(state_dir, e)
            return True
        except Exception as e:  # noqa: BLE001
            # Transient metadata read errors (GCS-style stores — exactly
            # the case the finalization check exists for) must NOT classify
            # an in-flight/torn checkpoint as durable (ADVICE r3). Skip it;
            # a genuinely durable step is re-discovered on the next probe.
            return self._probe_failed(state_dir, e)

    @staticmethod
    def _probe_failed(state_dir: str, e: Exception,
                      what: str = "durability probe") -> bool:
        import warnings

        # Routed through the bus as an event (counted by
        # tools/telemetry_report.py) so flaky-store noise is visible in
        # the JSONL stream, not just a stderr warning a supervisor log
        # rotation eats.
        telemetry_bus.emit("ckpt_probe_failed", what=what,
                           path=str(state_dir), error=repr(e))
        warnings.warn(f"checkpoint {what} failed for "
                      f"{state_dir}: {e!r}; treating as not durable")
        return False

    def steps(self) -> list:
        """All step numbers with a step_<n> dir, sorted (durable or not)."""
        return sorted(
            int(m.group(1)) for d in _listdir(self.directory)
            if (m := re.fullmatch(r"step_(\d+)", d)))

    def durable_steps(self) -> list:
        """Step numbers whose `state` checkpoint is fully committed."""
        return [s for s in self.steps()
                if self._is_durable(f"step_{s:08d}")]

    def latest_step(self) -> Optional[int]:
        """Newest *durable* checkpoint step — finalized, but NOT content-
        verified (prefer latest_valid_step, which is). An async save that
        has not committed yet (or a crashed one) is skipped rather than
        handed to restore (see _is_durable)."""
        steps = self.durable_steps()
        return max(steps) if steps else None

    def verify_step(self, step: int, deep: bool = True) -> VerifyResult:
        """Verify step's bytes against its commit manifest (see
        ckpt_integrity.verify_step_dir for the verdict semantics)."""
        return verify_step_dir(self._step_dir(step), deep=deep)

    def _report_corrupt(self, step: int, res: VerifyResult) -> None:
        telemetry_bus.emit("ckpt_corrupt", step=step,
                           failures=list(res.failures[:8]))
        print(f"[ckpt] step {step} failed verification "
              f"({'; '.join(res.failures[:3]) or res.status}); "
              f"falling back to an older checkpoint",
              file=sys.stderr, flush=True)

    def latest_valid_step(self) -> Optional[int]:
        """Newest step that is durable AND verifies against its commit
        manifest — what restore/auto-resume/rollback trust. Walks the
        lineage newest-first; every durable-but-corrupt step it skips on
        the way down emits a `ckpt_corrupt` telemetry event, so a flipped
        bit costs a logged fallback to the last known-good step instead
        of the run."""
        for step in sorted(self.durable_steps(), reverse=True):
            res = self.verify_step(step)
            if res.ok:
                return step
            self._report_corrupt(step, res)
        return None

    def valid_steps(self) -> list:
        """All durable steps that pass verification, sorted — the restore
        menu ckpt_doctor and explicit-step error messages show."""
        return [s for s in self.durable_steps() if self.verify_step(s).ok]

    def gc(self, dry_run: bool = False) -> dict:
        """Retention GC: prune step dirs per checkpoint.keep_last /
        keep_every; returns {"kept": [...], "deleted": [...]}. Runs after
        each durable commit (process 0 only — every other process sees
        the shared store mutate, same as it does for saves; and only
        post-commit, when no host can still be mid-restore: restores
        happen at startup/rollback, strictly before the subsequent save's
        commit). The last *verified* step is protected unconditionally —
        keep_last=1 with a corrupt newest step keeps the fallback alive.
        Only durable steps are candidates: a partially-written dir from a
        concurrent/crashed save is never touched."""
        ck = self.cfg.checkpoint
        if ck.keep_last <= 0:
            return {"kept": self.steps(), "deleted": []}
        durable = self.durable_steps()
        protect = set()
        last_valid = self.latest_valid_step()
        if last_valid is not None:
            protect.add(last_valid)
        keep, delete = retention_plan(durable, keep_last=ck.keep_last,
                                      keep_every=ck.keep_every,
                                      protect=protect)
        if not dry_run and jax.process_index() == 0:
            for s in delete:
                rmtree(self._step_dir(s))
            if delete:
                telemetry_bus.emit("ckpt_gc", deleted=delete, kept=keep)
        return {"kept": keep, "deleted": delete}

    def restore(self, state_template: TrainState,
                step: Optional[int] = None) -> tuple[TrainState, dict]:
        """Restore into the shardings/dtypes of `state_template` (any
        topology — resharding is Orbax's job). Returns (state, meta) where
        meta carries at least trained_tokens, plus the dataloader position
        when the checkpoint recorded one.

        With no explicit step this restores the newest durable AND
        verified checkpoint (latest_valid_step — the lineage-fallback
        path). An explicit step is validated the same way first, so a
        non-durable or corrupt request fails with the list of valid steps
        instead of a raw JSON/Orbax error mid-restore.
        """
        self.wait_until_finished()  # never read our own partial write
        if step is None:
            step = self.latest_valid_step()
            if step is None:
                raise FileNotFoundError(
                    f"no valid checkpoints under {self.directory}")
        else:
            if not self._is_durable(f"step_{step:08d}"):
                raise FileNotFoundError(
                    f"checkpoint step {step} under {self.directory} is "
                    f"missing or not durable (save incomplete/crashed); "
                    f"available valid steps: {self.valid_steps()}")
            res = self.verify_step(step)
            if not res.ok:
                self._report_corrupt(step, res)
                raise FileNotFoundError(
                    f"checkpoint step {step} under {self.directory} "
                    f"failed verification "
                    f"({'; '.join(res.failures[:3])}); available valid "
                    f"steps: {self.valid_steps()}")
        path = self._step_dir(step)

        def _read_meta():
            with open(os.path.join(path, "meta.json")) as f:
                return json.load(f)

        meta = retry_call(_read_meta, policy=self._retry,
                          describe=f"checkpoint meta read (step {step})")
        # Topology compatibility (resilience/elastic.py): a checkpoint
        # saved under a different mesh shape must never resume silently —
        # either hard-fail naming both topologies (elastic off) or
        # validate the constant-global-batch invariant and record the
        # resize (elastic on). Orbax handles the array resharding either
        # way; this guard handles the semantics. Runs before the uneven-PP
        # check so the operator-facing story leads with the topology.
        resize = elastic.check_restore_topology(
            path, meta, self.cfg, step=step, save_dir=self.directory)
        if resize is not None:
            # surfaced to the caller (train.build_state books/emits it);
            # never written back to disk
            meta["elastic_resize"] = resize
        # Checkpoints store the PP-padded layer stack. Even splits are
        # canonical (no padding), so any-topology restore works; an uneven
        # split bakes its pp into the padded shape, which a different pp
        # cannot consume — fail with the story rather than a shape error.
        # This is also the gate behind elastic pp resize: the guard above
        # admits a pp mismatch (checkpoint.elastic), and this check is
        # what restricts it to even splits that share the slot layout.
        src = meta.get("config", {})
        src_m, src_d = src.get("model", {}), src.get("distributed", {})
        if src_m.get("num_hidden_layers") and src_d.get("pp_size"):
            from picotron_tpu.models.llama import pp_layer_placement

            src_padded, src_slots = pp_layer_placement(
                src_m["num_hidden_layers"], src_d["pp_size"])
            dst_padded, dst_slots = pp_layer_placement(
                self.cfg.model.num_hidden_layers,
                self.cfg.distributed.pp_size)
            # Padded sizes alone can collide across pp_sizes (10 layers on
            # pp=3 and pp=4 both pad to 12) while placing real layers in
            # different slots — compare the slot layout itself.
            if src_padded != dst_padded or not np.array_equal(src_slots,
                                                              dst_slots):
                raise ValueError(
                    f"checkpoint was saved with an uneven PP layer split "
                    f"(padded stack {src_padded}, pp={src_d['pp_size']}) "
                    f"whose layer slots differ from this run's (padded "
                    f"stack {dst_padded}, pp="
                    f"{self.cfg.distributed.pp_size}); resume with the "
                    f"same pp_size or use a layer count divisible by both"
                )
        template = {
            "params": state_template.params,
            "opt_state": state_template.opt_state,
            "step": state_template.step,
        }
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            if hasattr(x, "sharding") else x,
            template,
        )
        restored = retry_call(
            self._ckptr.restore, os.path.join(path, "state"), abstract,
            policy=self._retry,
            describe=f"checkpoint restore (step {step})")
        # Force every leaf onto the template's sharding: Orbax can hand back
        # differently-placed arrays (e.g. scalar opt-state counters on a
        # single device), which would fail jit's consistent-devices check on
        # the first step after resume.
        restored = jax.tree.map(
            lambda r, t: jax.device_put(r, t.sharding)
            if hasattr(t, "sharding") else r,
            restored, template)
        state = TrainState(params=restored["params"],
                           opt_state=restored["opt_state"],
                           step=restored["step"])
        return state, meta


def restore_params_only(cfg: Config, ckpt_dir: str,
                        step: Optional[int] = None, dtype=None):
    """Restore ONLY the canonical [L]-stacked params from a training
    checkpoint onto the first local device — the inference/export path
    (tools/generate.py, tools/export_hf.py). Skips the Adam moments
    entirely (a partial PyTree restore: ~1/3 the IO and host memory of a
    full-state restore at 7B scale) and unpads the PP layer stack.

    `dtype` overrides the restored leaf dtype (Orbax casts DURING restore,
    so e.g. dtype=jnp.bfloat16 loads a 7B checkpoint in 13.5 GB without
    the 28 GB fp32 tree ever materializing — the single-chip decode path).
    For an optimizer_offload checkpoint the "params" entry is only the
    bf16 compute copy, so this restores the fp32 MASTER from
    opt_state.master instead — tools/export_hf.py must export full
    master precision, not bf16-rounded weights (code review r4)."""
    import orbax.checkpoint as ocp

    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.models.llama import unpad_layers

    menv = MeshEnv.create(dp=1, devices=jax.devices()[:1])
    mgr = CheckpointManager(cfg, menv, directory=ckpt_dir)
    if step is None:
        # Same trust rule as the training restore path: newest durable
        # AND manifest-verified — export/decode must not read a flipped
        # bit any more than resume may.
        step = mgr.latest_valid_step()
        if step is None:
            raise FileNotFoundError(f"no valid checkpoints under {ckpt_dir}")
    from picotron_tpu.parallel.api import abstract_master

    nl, pp = cfg.model.num_hidden_layers, cfg.distributed.pp_size
    abstract = abstract_master(cfg)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    restore_args = jax.tree.map(
        lambda x: ocp.ArrayRestoreArgs(dtype=dtype or x.dtype,
                                       sharding=sharding),
        abstract)
    if cfg.training.optimizer_offload:
        item = {"opt_state": {"master": abstract}}
        rargs = {"opt_state": {"master": restore_args}}
        pick = lambda r: r["opt_state"]["master"]  # noqa: E731
    else:
        item = {"params": abstract}
        rargs = {"params": restore_args}
        pick = lambda r: r["params"]  # noqa: E731
    # partial_restore: skip the tree branches absent from `item`
    with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
        restored = ckptr.restore(
            os.path.join(mgr.directory, f"step_{step:08d}", "state"),
            args=ocp.args.PyTreeRestore(
                item=item, restore_args=rargs, partial_restore=True))
    return unpad_layers(pick(restored), nl, pp), step


# ---------------------------------------------------------------------------
# HF safetensors import (ref: checkpoint.py:50-230)
# ---------------------------------------------------------------------------

# safetensors name -> (our key path, needs_transpose). Torch Linear stores
# [out_features, in_features]; our matmuls are x @ w with [in, out]
# (the reference's regex rename map is checkpoint.py:213-230).
_ATTN_MAP = {
    "self_attn.q_proj.weight": ("q", True),
    "self_attn.k_proj.weight": ("k", True),
    "self_attn.v_proj.weight": ("v", True),
    "self_attn.o_proj.weight": ("o", True),
    "input_layernorm.weight": ("input_norm", False),
    "post_attention_layernorm.weight": ("post_norm", False),
}

_LAYER_MAP = {
    **_ATTN_MAP,
    "mlp.gate_proj.weight": ("gate", True),
    "mlp.up_proj.weight": ("up", True),
    "mlp.down_proj.weight": ("down", True),
}

# Qwen2-style qkv bias (HF stores [out_features]; no transpose).
_BIAS_MAP = {
    "self_attn.q_proj.bias": ("b_q", False),
    "self_attn.k_proj.bias": ("b_k", False),
    "self_attn.v_proj.bias": ("b_v", False),
}

# Mixtral MoE expert naming: block_sparse_moe.experts.<j>.{w1,w2,w3} hold
# gate/down/up projections, block_sparse_moe.gate is the router.
_MOE_EXPERT_MAP = {"w1": "w_gate", "w2": "w_down", "w3": "w_up"}


def _read_safetensors_dir(path: str) -> dict[str, np.ndarray]:
    """Read all tensors from a single-file or index-sharded HF safetensors
    checkpoint directory (ref: checkpoint.py:62-86 handles both layouts)."""
    from safetensors.numpy import load_file

    index_path = os.path.join(path, "model.safetensors.index.json")
    single_path = os.path.join(path, "model.safetensors")
    tensors: dict[str, np.ndarray] = {}
    if os.path.exists(index_path):
        with open(index_path) as f:
            index = json.load(f)
        for shard in sorted(set(index["weight_map"].values())):
            tensors.update(load_file(os.path.join(path, shard)))
    elif os.path.exists(single_path):
        tensors.update(load_file(single_path))
    else:
        raise FileNotFoundError(
            f"no model.safetensors[.index.json] under {path}")
    return tensors


def load_hf_safetensors(path: str, cfg: ModelConfig,
                        dtype=jnp.float32) -> dict[str, Any]:
    """Materialize an HF Llama-family safetensors checkpoint as our stacked
    param pytree (fp32 master by default)."""
    if cfg.qk_norm:
        # OLMoE's tensor names (mlp.gate, mlp.experts.N.gate_proj,
        # self_attn.q_norm) have no map here yet; loading the Mixtral
        # names would leave the layer tree without its q/k norm weights
        raise NotImplementedError(
            "load_hf_safetensors has no tensor-name map for qk_norm "
            "(OLMoE) checkpoints")
    raw = _read_safetensors_dir(path)
    nl = cfg.num_hidden_layers
    file_layers = {int(mm.group(1)) for k in raw
                   if (mm := re.match(r"model\.layers\.(\d+)\.", k))}
    if file_layers and len(file_layers) != nl:
        # A config expecting FEWER layers than the file holds would
        # otherwise silently truncate the model (more layers fails later
        # with a missing-tensor KeyError, but make both cases explicit).
        raise ValueError(
            f"checkpoint at {path} has {len(file_layers)} layers but the "
            f"config expects num_hidden_layers={nl}; pass a matching model "
            f"config")

    def get(name: str) -> np.ndarray:
        if name not in raw:
            raise KeyError(
                f"tensor {name!r} missing from checkpoint (found "
                f"{len(raw)} tensors)")
        return raw[name].astype(np.float32)

    lmap = dict(_ATTN_MAP if cfg.num_experts else _LAYER_MAP)
    if cfg.attention_bias:
        lmap.update(_BIAS_MAP)
    layers: dict[str, list[np.ndarray]] = {k: [] for k, _ in lmap.values()}
    if cfg.num_experts:
        layers.update({k: [] for k in ("router", "w_gate", "w_up", "w_down")})
    for i in range(nl):
        prefix = f"model.layers.{i}."
        for suffix, (key, transpose) in lmap.items():
            t = get(prefix + suffix)
            layers[key].append(t.T if transpose else t)
        if cfg.num_experts:
            moe = prefix + "block_sparse_moe."
            layers["router"].append(get(moe + "gate.weight").T)  # [H, E]
            for short, key in _MOE_EXPERT_MAP.items():
                bank = [get(f"{moe}experts.{j}.{short}.weight").T
                        for j in range(cfg.num_experts)]
                layers[key].append(np.stack(bank))  # [E, in, out]

    embedding = get("model.embed_tokens.weight")  # [vocab, hidden]
    params = {
        "embedding": jnp.asarray(embedding, dtype),
        "layers": {k: jnp.asarray(np.stack(v), dtype)
                   for k, v in layers.items()},
        "final_norm": jnp.asarray(get("model.norm.weight"), dtype),
    }
    if cfg.tie_word_embeddings:
        # Qwen2-style tying: no lm_head parameter; head_weight() reads the
        # embedding. (A stray lm_head.weight in the file is ignored — HF
        # does the same for tied configs.)
        return params
    if "lm_head.weight" in raw:
        lm_head = get("lm_head.weight").T  # [hidden, vocab]
    else:
        # Tied-head checkpoint loaded as an UNTIED model: untie by copying
        # (ref: checkpoint.py:88-91 force-creates lm_head the same way).
        lm_head = embedding.T.copy()
    params["lm_head"] = jnp.asarray(lm_head, dtype)
    return params


def save_hf_safetensors(params: dict[str, Any], path: str) -> None:
    """Export our param pytree to HF Llama safetensors naming (round-trip of
    `load_hf_safetensors`; the reference has no export path)."""
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    out: dict[str, np.ndarray] = {}
    out["model.embed_tokens.weight"] = np.asarray(params["embedding"])
    out["model.norm.weight"] = np.asarray(params["final_norm"])
    if "lm_head" in params:  # tied models carry no separate head
        out["lm_head.weight"] = np.asarray(params["lm_head"]).T
    layers = params["layers"]
    if "q_norm" in layers:
        raise NotImplementedError(
            "save_hf_safetensors has no tensor-name map for qk_norm "
            "(OLMoE) checkpoints")
    nl = next(iter(layers.values())).shape[0]
    is_moe = "router" in layers
    lmap = dict(_ATTN_MAP if is_moe else _LAYER_MAP)
    if "b_q" in layers:
        lmap.update(_BIAS_MAP)
    for i in range(nl):
        prefix = f"model.layers.{i}."
        for suffix, (key, transpose) in lmap.items():
            t = np.asarray(layers[key][i])
            out[prefix + suffix] = t.T if transpose else t
        if is_moe:
            moe = prefix + "block_sparse_moe."
            out[moe + "gate.weight"] = np.asarray(layers["router"][i]).T
            for short, key in _MOE_EXPERT_MAP.items():
                bank = np.asarray(layers[key][i])  # [E, in, out]
                for j in range(bank.shape[0]):
                    out[f"{moe}experts.{j}.{short}.weight"] = bank[j].T
    out = {k: np.ascontiguousarray(v) for k, v in out.items()}
    save_file(out, os.path.join(path, "model.safetensors"))
