#!/usr/bin/env python
"""Experiment config generator — parity with the reference's create_config.py.

Writes `<out_dir>/<exp_name>/config.json` in the (reference-compatible) JSON
schema from CLI flags (ref: create_config.py:78-106), prints the global-batch
math (ref: create_config.py:71-73). Model hyperparameters resolve from the
built-in preset registry instead of a network AutoConfig fetch
(ref: create_config.py:51-55) — TPU pods frequently have zero egress.

Example:
  python tools/create_config.py --exp-name smol-dp4tp2 --out-dir runs \\
      --model SmolLM-1.7B --dp 4 --tp 2 --pp 2 --seq-len 2048 \\
      --mbs 4 --grad-acc 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from picotron_tpu.config import config_from_dict, resolve_preset  # noqa: E402


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="picotron-tpu config generator")
    p.add_argument("--exp-name", required=True)
    p.add_argument("--out-dir", default="runs")
    # parallel layout (ref: create_config.py --tp/--cp/--dp/--pp)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--cp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1,
                   help="expert parallelism (MoE models only)")
    p.add_argument("--pp-engine", default="1f1b", choices=["1f1b", "afab"])
    p.add_argument("--cp-flavor", default=None,
                   choices=["ring", "ulysses", "mesh"],
                   help="context-parallel attention schedule for cp > 1 "
                        "(default: ring, or whatever --attn-impl names); "
                        "'mesh' factors cp into a 2D submesh — see "
                        "--cp-mesh")
    p.add_argument("--cp-mesh", default=None, metavar="XxY",
                   help="mesh-flavor factorization cp = cp_x * cp_y, e.g. "
                        "'2x4' (default: most-square feasible split; "
                        "cp_y must divide the tp-local head counts)")
    p.add_argument("--sequence-parallel", action="store_true",
                   help="Megatron-SP over the tp axis (seq-sharded "
                        "residual stream between blocks)")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1: shard Adam moments over dp")
    p.add_argument("--slices", type=int, default=None,
                   help="multislice topology: the pod spans N TPU slices "
                        "joined by DCN; dp then pp absorb the slice "
                        "granules (mesh.py)")
    # model
    p.add_argument("--model", default="HuggingFaceTB/SmolLM-1.7B")
    p.add_argument("--from-hf-config", default=None, metavar="CONFIG_JSON",
                   help="resolve model hyperparameters from a local HF "
                        "config.json instead of the preset registry — the "
                        "offline AutoConfig: any Llama/Qwen2/Mixtral-"
                        "family model trains without hand-typing its "
                        "architecture (--model then only names the run)")
    p.add_argument("--num-hidden-layers", type=int, default=None,
                   help="override the preset's layer count "
                        "(ref: create_config.py:56-59)")
    p.add_argument("--num-attention-heads", type=int, default=None)
    p.add_argument("--num-key-value-heads", type=int, default=None)
    p.add_argument("--attn-impl", default="auto",
                   choices=["auto", "flash", "reference", "ring",
                            "ulysses", "mesh"])
    p.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    # training (ref: create_config.py --mbs/--grad-acc/--seq-len)
    p.add_argument("--mbs", type=int, default=1)
    p.add_argument("--grad-acc", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--learning-rate", type=float, default=3e-4)
    p.add_argument("--lr-schedule", default="constant",
                   choices=["constant", "cosine", "linear"])
    p.add_argument("--lr-warmup-steps", type=int, default=0)
    p.add_argument("--total-train-steps", type=int, default=200)
    p.add_argument("--eval-frequency", type=int, default=0,
                   help="run a val-loss pass every N steps (0 = off); HF "
                        "datasets need --eval-split")
    p.add_argument("--eval-steps", type=int, default=8)
    p.add_argument("--eval-split", default=None)
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--remat-policy", default="dots",
                   choices=["full", "dots", "dots_attn", "dots_lean", "dots_norms",
                            "dots_offload"])
    p.add_argument("--adam-moments-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="bf16 halves optimizer-state memory (update math "
                        "stays fp32) — usually required to fit >1B models "
                        "per 16G chip; check with tools/memcheck.py")
    p.add_argument("--optimizer-offload", action="store_true",
                   help="fp32 master + Adam moments in pinned HOST memory "
                        "(the full-depth-on-one-chip lever; pair with "
                        "--grad-acc >= 16 to amortize the PCIe round "
                        "trip; requires bf16 model dtype)")
    p.add_argument("--grad-engine", default="auto",
                   choices=["auto", "ad", "fused"],
                   help="'fused' accumulates per-layer dW in-scan (no "
                        "per-microbatch grad tree; any pp=1 layout incl. "
                        "tp/SP/cp ring|ulysses/MoE/ep, with "
                        "remat_policy=dots_attn — see the README "
                        "eligibility matrix); 'auto' picks it whenever "
                        "supported")
    # dataset
    p.add_argument("--dataset", default="synthetic")
    p.add_argument("--subset", default=None)
    p.add_argument("--split", default="train")
    p.add_argument("--tokenizer", default=None)
    # serving (picotron_tpu/serve: continuous batching + paged KV cache)
    p.add_argument("--serve-slots", type=int, default=None,
                   help="serving decode batch width (writes the `serve` "
                        "config block; picotron_tpu/serve)")
    p.add_argument("--serve-block-size", type=int, default=None,
                   help="tokens per paged-KV-cache block")
    p.add_argument("--serve-num-blocks", type=int, default=None,
                   help="physical blocks in the shared KV pool (0 = "
                        "worst-case auto; set lower to oversubscribe — "
                        "the scheduler preempts youngest-first)")
    p.add_argument("--serve-prefill-chunk", type=int, default=None,
                   help="prompt tokens prefilled per engine iteration")
    p.add_argument("--serve-max-len", type=int, default=None,
                   help="per-sequence serving capacity (0 = the model's "
                        "max_position_embeddings)")
    p.add_argument("--serve-decode-interval", type=int, default=None,
                   help="decode steps scanned per dispatch (amortizes "
                        "host overhead; retirement latency quantizes "
                        "to it)")
    # checkpoint / logging
    p.add_argument("--save-frequency", type=int, default=0)
    p.add_argument("--auto-resume", action="store_true",
                   help="resume from the newest durable checkpoint in the "
                        "save dir when the job (re)starts — pairs with "
                        "submit_jobs' failure resubmission so preempted "
                        "jobs continue instead of restarting")
    p.add_argument("--download-model", action="store_true",
                   help="snapshot the model's HF safetensors (tools/"
                        "download_model.py; ref: create_config.py:134) and "
                        "set checkpoint.init_from_hf so training starts "
                        "from the pretrained weights")
    p.add_argument("--use-wandb", action="store_true")
    p.add_argument("--use-cpu", action="store_true",
                   help="run the layout on simulated host devices (the "
                        "reference's --use_cpu, ref: create_config.py:64-66)")
    return p


def create_single_config(args) -> str:
    model_overrides = {
        k: v for k, v in dict(
            num_hidden_layers=args.num_hidden_layers,
            num_attention_heads=args.num_attention_heads,
            num_key_value_heads=args.num_key_value_heads,
        ).items() if v is not None
    }
    if getattr(args, "from_hf_config", None):
        # offline long-tail resolution: any Llama-family model outside the
        # preset registry, from its local HF config.json (the reference
        # fetches this over the network via AutoConfig,
        # ref: create_config.py:51-55; zero-egress pods can't)
        from picotron_tpu.config import model_config_from_hf_json

        preset = model_config_from_hf_json(args.from_hf_config)
    else:
        preset = resolve_preset(args.model)
    seq_len = args.seq_len
    if seq_len > preset["max_position_embeddings"]:
        preset["max_position_embeddings"] = seq_len

    raw = {
        "distributed": {
            "tp_size": args.tp, "cp_size": args.cp, "pp_size": args.pp,
            "dp_size": args.dp, "ep_size": args.ep,
            "pp_engine": args.pp_engine,
            "sequence_parallel": args.sequence_parallel,
            "zero1": args.zero1,
            "use_cpu": args.use_cpu,
            **({"cp_flavor": args.cp_flavor} if args.cp_flavor else {}),
            **({"cp_mesh": args.cp_mesh} if args.cp_mesh else {}),
            **({"slices": args.slices} if args.slices else {}),
        },
        "model": {
            "name": args.model, **preset, **model_overrides,
            "dtype": args.dtype, "attn_impl": args.attn_impl,
        },
        "training": {
            "seq_length": seq_len,
            "micro_batch_size": args.mbs,
            "gradient_accumulation_steps": args.grad_acc,
            "learning_rate": args.learning_rate,
            "lr_schedule": args.lr_schedule,
            "lr_warmup_steps": args.lr_warmup_steps,
            "total_train_steps": args.total_train_steps,
            "eval_frequency": args.eval_frequency,
            "eval_steps": args.eval_steps,
            "adam_moments_dtype": args.adam_moments_dtype,
            "optimizer_offload": args.optimizer_offload,
            "remat": not args.no_remat,
            "remat_policy": args.remat_policy,
            "grad_engine": args.grad_engine,
        },
        "dataset": {
            "name": args.dataset, "subset_name": args.subset,
            "split": args.split, "eval_split": args.eval_split,
            "tokenizer_name": args.tokenizer,
        },
        # save_dir pinned INSIDE the run directory: the dataclass default
        # ("ckpt") is relative, and submit_jobs launches trainers with
        # cwd=REPO_ROOT — checkpoints and telemetry.jsonl from every run
        # would otherwise pile into one shared repo-root ckpt/ (and
        # extract_metrics could never pair a run with its telemetry).
        "checkpoint": {"save_frequency": args.save_frequency,
                       "auto_resume": args.auto_resume,
                       "save_dir": os.path.abspath(os.path.join(
                           args.out_dir, args.exp_name, "ckpt"))},
        "logging": {"use_wandb": args.use_wandb, "run_name": args.exp_name},
    }
    serve = {k: v for k, v in dict(
        decode_slots=args.serve_slots,
        block_size=args.serve_block_size,
        num_blocks=args.serve_num_blocks,
        prefill_chunk=args.serve_prefill_chunk,
        max_model_len=args.serve_max_len,
        decode_interval=args.serve_decode_interval,
    ).items() if v is not None}
    if serve:
        raw["serve"] = serve
    if getattr(args, "download_model", False):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from download_model import download

        from picotron_tpu.config import resolve_hf_name

        raw["checkpoint"]["init_from_hf"] = download(
            resolve_hf_name(args.model))
    cfg = config_from_dict(raw)  # validates

    exp_dir = os.path.join(args.out_dir, args.exp_name)
    os.makedirs(exp_dir, exist_ok=True)
    path = os.path.join(exp_dir, "config.json")
    with open(path, "w") as f:
        json.dump(raw, f, indent=2)

    # ref: create_config.py:71-73 prints the same math
    print(f"config -> {path}")
    print(f"  mesh: dp={args.dp} pp={args.pp} ep={args.ep} cp={args.cp} tp={args.tp} "
          f"({cfg.distributed.world_size} chips)")
    dataxes = (f"x dp {args.dp} x ep {args.ep}" if args.ep > 1
               else f"x dp {args.dp}")
    print(f"  global_batch_size = mbs {args.mbs} x grad_acc {args.grad_acc} "
          f"{dataxes} = {cfg.global_batch_size} "
          f"({cfg.tokens_per_step} tokens/step)")
    return path


if __name__ == "__main__":
    create_single_config(build_parser().parse_args())
