import json

import pytest

from picotron_tpu.config import (
    Config,
    config_from_dict,
    load_config,
    num_params,
    resolve_preset,
)


def test_defaults_validate():
    cfg = Config()
    cfg.validate()
    assert cfg.distributed.world_size == 1


def test_reference_schema_loads(tmp_path):
    # A verbatim reference-style config (schema of template/base_config.json)
    raw = {
        "distributed": {
            "tp_size": 2, "cp_size": 1, "pp_size": 2, "dp_size": 2,
            "pp_engine": "1f1b", "backend": "nccl", "use_cpu": False,
        },
        "model": {
            "name": "HuggingFaceTB/SmolLM-360M-Instruct",
            "num_hidden_layers": 16,
            "num_attention_heads": 16,
            "num_key_value_heads": 4,
            "dtype": "bfloat16",
            "use_flash_attention": True,
            "use_fused_adam": True,
        },
        "training": {
            "seed": 42, "learning_rate": 3e-4, "total_train_steps": 200,
            "seq_length": 1024, "micro_batch_size": 32,
            "gradient_accumulation_steps": 1, "num_samples": 400000,
            "max_tokens": None,
        },
        "dataset": {"name": "roneneldan/TinyStories", "subset_name": None,
                    "num_workers": 0, "num_proc": 1},
        "checkpoint": {"save_dir": "ckpt", "save_frequency": 300, "load_path": ""},
        "logging": {"use_wandb": False, "project_name": "picotron", "run_name": None},
        "environment": {"OMP_NUM_THREADS": "1", "FLASH_ATTEN": "1", "HF_TOKEN": None},
    }
    p = tmp_path / "config.json"
    p.write_text(json.dumps(raw))
    cfg = load_config(str(p))
    # overrides beat the preset
    assert cfg.model.num_hidden_layers == 16
    assert cfg.model.num_key_value_heads == 4
    # preset fills the rest
    assert cfg.model.hidden_size == 960
    assert cfg.model.vocab_size == 49152
    assert cfg.global_batch_size == 32 * 1 * 2
    assert cfg.tokens_per_step == 64 * 1024


def test_preset_aliases():
    assert resolve_preset("SmolLM-1.7B")["hidden_size"] == 2048
    assert resolve_preset("Llama-2-7B")["num_hidden_layers"] == 32
    with pytest.raises(KeyError):
        resolve_preset("nonexistent-model")


def test_validation_errors():
    with pytest.raises(ValueError):
        config_from_dict({"distributed": {"tp_size": 3},
                          "model": {"name": "debug-tiny"}})  # 4 heads % 3 != 0
    with pytest.raises(ValueError):
        config_from_dict({"distributed": {"cp_size": 3},
                          "model": {"name": "debug-tiny"},
                          "training": {"seq_length": 128}})  # 128 % 3 != 0


def test_num_params_llama2_7b():
    from picotron_tpu.config import ModelConfig
    m = ModelConfig(name="meta-llama/Llama-2-7b-hf", **resolve_preset("Llama-2-7B"))
    n = num_params(m)
    # ~6.74B params + untied head
    assert 6.5e9 < n < 7.1e9


def test_ce_chunk_and_lr_ratio_validation():
    import pytest

    from picotron_tpu.config import Config, TrainingConfig

    with pytest.raises(ValueError, match="ce_chunk_size"):
        Config(training=TrainingConfig(ce_chunk_size=-16)).validate()
    # non-dividing chunk would silently fall back to fused — reject
    with pytest.raises(ValueError, match="divide"):
        Config(training=TrainingConfig(ce_chunk_size=100)).validate()
    Config(training=TrainingConfig(ce_chunk_size=64)).validate()  # 256 % 64
    # chunk >= vocab shard would silently degenerate to the fused CE path —
    # the exact fallback the user set the knob to avoid (ADVICE r3)
    with pytest.raises(ValueError, match="smaller"):
        Config(training=TrainingConfig(ce_chunk_size=512)).validate()
    with pytest.raises(ValueError, match="lr_min_ratio"):
        Config(training=TrainingConfig(lr_min_ratio=-0.1)).validate()


def test_serve_config_validation():
    import pytest

    from picotron_tpu.config import Config, ModelConfig, ServeConfig

    Config().validate()  # defaults carry a valid serve block
    for bad in (dict(decode_slots=0), dict(block_size=0),
                dict(prefill_chunk=0), dict(decode_interval=0),
                dict(num_blocks=-1), dict(max_model_len=-1)):
        with pytest.raises(ValueError, match="serve"):
            Config(serve=ServeConfig(**bad)).validate()
    # per-sequence serving capacity cannot exceed the model's positions
    from picotron_tpu.config import TrainingConfig

    small = TrainingConfig(seq_length=64)
    with pytest.raises(ValueError, match="max_model_len"):
        Config(model=ModelConfig(max_position_embeddings=128),
               training=small,
               serve=ServeConfig(max_model_len=256)).validate()
    Config(model=ModelConfig(max_position_embeddings=256),
           training=small,
           serve=ServeConfig(max_model_len=256)).validate()


def test_serve_config_from_dict_round_trip():
    from picotron_tpu.config import config_from_dict

    cfg = config_from_dict({
        "model": {"name": "debug-tiny"},
        "serve": {"decode_slots": 4, "block_size": 8, "num_blocks": 16,
                  "prefill_chunk": 32, "max_model_len": 256,
                  "decode_interval": 2},
    })
    assert cfg.serve.decode_slots == 4 and cfg.serve.num_blocks == 16
    assert cfg.serve.decode_interval == 2
    # unknown keys in the section are ignored (reference-JSON compat)
    cfg2 = config_from_dict({"model": {"name": "debug-tiny"},
                             "serve": {"decode_slots": 2, "bogus": 1}})
    assert cfg2.serve.decode_slots == 2


# ---------------------------------------------------------------------------
# retired options fail loudly (unknown keys are otherwise ignored on load)
# ---------------------------------------------------------------------------

# section.key: (former default, a value that used to select other code); one
# table and one check in config.py for both sections
_RETIRED = {
    "distributed.tp_strategy": ("megatron", "2d"),
    "distributed.tp_sync": ("sync", "deferred"),
    "distributed.tp_mesh": ("", "2x2"),
    "distributed.dcn_axes": ("dp,pp", "dp"),
    "distributed.hier_dp_reduce": ("auto", "on"),
    "serve.speculator": ("off", "ngram"),
    "serve.draft_len": (3, 2),
    "serve.disagg": (False, True),
    "serve.prefill_slots": (0, 4),
    "serve.prefill_num_blocks": (0, 64),
    "serve.prefill_device": (-1, 1),
    "serve.decode_device": (-1, 0),
}


@pytest.mark.parametrize("how", ["former-default", "other-value"])
@pytest.mark.parametrize("name", sorted(_RETIRED))
def test_retired_option(name, how):
    former, other = _RETIRED[name]
    section, key = name.split(".")
    raw = {"distributed": {"tp_size": 2, "dp_size": 2},
           "serve": {"decode_slots": 2}}
    raw[section][key] = former if how == "former-default" else other
    if how == "former-default":
        # a dumped config of an old run: loads, and the key is dropped
        cfg = config_from_dict(raw)
        assert (cfg.distributed.tp_size, cfg.serve.decode_slots) == (2, 2)
        assert key not in cfg.to_json_dict()[section]
    else:
        with pytest.raises(ValueError, match=f"{section}.{key}.*removed"):
            config_from_dict(raw)


@pytest.mark.parametrize("spelling", ['distributed.tp_sync="deferred"',
                                      "distributed.tp_sync=deferred"])
def test_retired_option_through_cli_override(tmp_path, monkeypatch, spelling):
    """tools/memcheck.py --override is the CLI's way to set a knob: a
    retired one must stop the run, not measure the schedule that remains.
    JSON-quoted it reaches the loader, which names the removal; bare it is
    refused earlier, as any string for a field that does not exist."""
    from tests.test_tools import load_tool

    path = tmp_path / "cfg.json"
    path.write_text(json.dumps({"distributed": {"tp_size": 2}}))
    monkeypatch.setattr("sys.argv", ["memcheck.py", "--config", str(path),
                                     "--override", spelling])
    with pytest.raises((ValueError, SystemExit), match="tp_sync") as exc:
        load_tool("memcheck").main()
    if '"' in spelling:
        assert "removed" in str(exc.value)


def test_slices_must_divide_dp_times_pp():
    # mesh._split_axes_over_dcn's rule, refused at load: ep/cp/tp
    # collectives never cross the slice cut
    ok = config_from_dict({"distributed": {"dp_size": 2, "tp_size": 2,
                                           "slices": 2}})
    assert ok.distributed.slices == 2
    with pytest.raises(ValueError, match="slices"):
        config_from_dict({"distributed": {"dp_size": 2, "tp_size": 2,
                                          "slices": 3}})
