"""Config system parity (SURVEY.md §2a #9): schema, provenance copy,
world-size derivation, reference-GPU-schema compatibility."""

import os

import pytest
import yaml

from tpuddp import config as cfg


def write_settings(tmp_path, data):
    p = tmp_path / "settings.yaml"
    p.write_text(yaml.dump(data))
    return str(p)


BASE = {
    "script_path": "train_native.py",
    "out_dir": None,  # filled per test
    "optional_args": {"set_epoch": True, "print_rand": False},
    "local": {"device": "tpu", "tpu": {"num_chips": 8}},
}


def test_load_and_prepare_out_dir_copies_settings(tmp_path):
    data = dict(BASE, out_dir=str(tmp_path / "out"))
    path = write_settings(tmp_path, data)
    settings = cfg.load_settings(path)
    out_dir = cfg.prepare_out_dir(settings, path)
    assert os.path.isdir(out_dir)
    copied = os.path.join(out_dir, "settings.yaml")
    assert os.path.exists(copied)  # provenance copy (reference :300-303)
    assert yaml.safe_load(open(copied))["script_path"] == "train_native.py"


def test_world_size_from_tpu_block(tmp_path):
    assert cfg.world_size_from(BASE) == 8


def test_world_size_from_reference_condor_schema():
    settings = {"local": {"device": "cuda", "condor": {"num_gpus": 2}}}
    assert cfg.world_size_from(settings) == 2
    assert cfg.device_from(settings) is None  # cuda maps onto the ladder


def test_world_size_absent_is_none():
    assert cfg.world_size_from({"local": {}}) is None


def test_world_size_env_override_wins(monkeypatch):
    """$TPUDDP_WORLD_SIZE (the restart supervisor's elastic shrink lever)
    beats the settings file on both entrypoints' resolution path."""
    monkeypatch.setenv("TPUDDP_WORLD_SIZE", "2")
    assert cfg.world_size_from(BASE) == 2
    assert cfg.world_size_from({"local": {}}) == 2
    monkeypatch.delenv("TPUDDP_WORLD_SIZE")
    assert cfg.world_size_from(BASE) == 8


def test_device_validation():
    assert cfg.device_from({"local": {"device": "cpu"}}) == "cpu"
    with pytest.raises(ValueError):
        cfg.device_from({"local": {"device": "mps"}})


def test_training_defaults_match_reference_constants():
    t = cfg.training_config({})
    # BASELINE.md workload constants
    assert t["train_batch_size"] == 128
    assert t["test_batch_size"] == 100
    assert t["learning_rate"] == 0.001
    assert t["num_epochs"] == 20
    assert t["checkpoint_epoch"] == 5
    assert t["image_size"] == 224


def test_training_overrides_merge():
    t = cfg.training_config({"training": {"model": "toy_mlp", "num_epochs": 2}})
    assert t["model"] == "toy_mlp"
    assert t["num_epochs"] == 2
    assert t["train_batch_size"] == 128  # default retained


def test_repo_example_settings_parse():
    settings = cfg.load_settings("local_settings.yaml")
    assert cfg.world_size_from(settings) == 8
    assert cfg.optional_args_from(settings) == {
        "set_epoch": True,
        "print_rand": False,
    }


def test_rendezvous_absent_is_empty():
    assert cfg.rendezvous_from({}) == {}
    assert cfg.rendezvous_from({"local": {}}) == {}


def test_rendezvous_block_parses():
    s = {"local": {"rendezvous": {
        "coordinator_address": "10.0.0.1:8476",
        "num_processes": 4,
        "process_id": 2,
    }}}
    assert cfg.rendezvous_from(s) == {
        "coordinator_address": "10.0.0.1:8476",
        "num_processes": 4,
        "process_id": 2,
    }


def test_rendezvous_env_overrides(monkeypatch):
    """One shared YAML across hosts: the launcher sets the per-host id in the
    environment (torchrun's RANK analog)."""
    s = {"local": {"rendezvous": {
        "coordinator_address": "10.0.0.1:8476", "num_processes": 2,
    }}}
    with pytest.raises(ValueError):  # num_processes>1 needs a process id
        cfg.rendezvous_from(s)
    monkeypatch.setenv("TPUDDP_PROCESS_ID", "1")
    assert cfg.rendezvous_from(s)["process_id"] == 1
    monkeypatch.setenv("TPUDDP_COORDINATOR", "10.0.0.9:9999")
    monkeypatch.setenv("TPUDDP_NUM_PROCESSES", "8")
    out = cfg.rendezvous_from({})
    assert out == {
        "coordinator_address": "10.0.0.9:9999",
        "num_processes": 8,
        "process_id": 1,
    }


def test_rendezvous_unknown_key_rejected():
    with pytest.raises(ValueError):
        cfg.rendezvous_from({"local": {"rendezvous": {"master_addr": "x"}}})


def test_num_classes_derived_from_dataset():
    assert cfg.num_classes_from({"dataset": "cifar10"}) == 10
    assert cfg.num_classes_from({"dataset": "digits"}) == 10
    assert cfg.num_classes_from({}) == 10  # default dataset is cifar10


def test_num_classes_explicit_overrides_dataset():
    assert cfg.num_classes_from({"dataset": "cifar10", "num_classes": 7}) == 7


def test_num_classes_unknown_dataset_requires_explicit():
    with pytest.raises(ValueError, match="num_classes"):
        cfg.num_classes_from({"dataset": "imagenet21k"})


def test_example_configs_parse_and_validate(monkeypatch):
    """Every YAML under configs/ must parse, produce a valid training config,
    and resolve rendezvous/world-size without error."""
    import glob

    for var in ("TPUDDP_COORDINATOR", "TPUDDP_NUM_PROCESSES", "TPUDDP_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = sorted(glob.glob(os.path.join(repo, "configs", "*.yaml")))
    assert len(paths) >= 4
    for p in paths:
        settings = cfg.load_settings(p)
        training = cfg.training_config(settings)
        assert cfg.num_classes_from(training) == 10
        cfg.world_size_from(settings)
        cfg.device_from(settings)
        if "rendezvous" in settings.get("local", {}):
            monkeypatch.setenv("TPUDDP_PROCESS_ID", "0")
            rdv = cfg.rendezvous_from(settings)
            assert rdv["coordinator_address"]


def test_rendezvous_multiprocess_requires_coordinator_on_cpu(monkeypatch):
    for var in ("TPUDDP_COORDINATOR", "TPUDDP_NUM_PROCESSES", "TPUDDP_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    rdv = {"rendezvous": {"num_processes": 2, "process_id": 0}}
    # CPU dev rung has no auto-discovery: coordinator required
    with pytest.raises(ValueError, match="coordinator_address"):
        cfg.rendezvous_from({"local": dict(rdv, device="cpu")})
    # TPU pods auto-discover peers: no coordinator needed
    out = cfg.rendezvous_from({"local": dict(rdv, device="tpu")})
    assert out == {"num_processes": 2, "process_id": 0}
    # pod auto-discovery may omit process_id too
    out = cfg.rendezvous_from(
        {"local": {"device": "tpu", "rendezvous": {"num_processes": 2}}}
    )
    assert out == {"num_processes": 2}


def test_training_config_refuses_unknown_keys():
    """A typo'd training knob must fail loudly with a did-you-mean, not be
    silently ignored (which would train a different config than the file
    says)."""
    with pytest.raises(ValueError, match="wieght_update_sharding.*did you mean.*weight_update_sharding"):
        cfg.training_config({"training": {"wieght_update_sharding": True}})
    with pytest.raises(ValueError, match="unknown training key"):
        cfg.training_config({"training": {"zzz_not_a_knob": 1}})
    # every documented key still passes
    ok = cfg.training_config({"training": {"resume": True, "synthetic_n": [64, 32]}})
    assert ok["resume"] is True and ok["synthetic_n"] == [64, 32]


def _overlap_in_settings():
    cfg.training_config({"training": {"comm_overlap": "auto"}})


def _overlap_to_the_wrap():
    from tpuddp import nn, optim
    from tpuddp.models import ToyMLP
    from tpuddp.parallel.ddp import DistributedDataParallel

    DistributedDataParallel(ToyMLP(), optim.Adam(1e-3), nn.CrossEntropyLoss(), comm_overlap=False)


def _overlap_to_the_accelerator():
    from tpuddp.accelerate import Accelerator

    Accelerator(comm_overlap="auto")


@pytest.mark.parametrize("give,error,says", [
    (_overlap_in_settings, ValueError, "unknown training key.*comm_overlap"),
    (_overlap_to_the_wrap, TypeError, "unexpected keyword argument 'comm_overlap'"),
    (_overlap_to_the_accelerator, TypeError, "unexpected keyword argument 'comm_overlap'"),
], ids=["settings", "ddp", "accelerator"])
def test_the_removed_overlap_knob_is_refused_as_unknown(give, error, says):
    """The step is one program, so ``comm_overlap`` selects nothing: a settings
    file or a caller that still names it is refused like any stale key, by the
    checks that refuse every unknown name (no special case, no shim)."""
    with pytest.raises(error, match=says):
        give()


def test_serving_config_defaults_and_merge():
    out = cfg.serving_config({})
    assert out == cfg.SERVING_DEFAULTS
    out = cfg.serving_config(
        {"serving": {"model": "alexnet", "num_replicas": 4,
                     "per_tenant_quota": 8}}
    )
    assert out["model"] == "alexnet"
    assert out["num_replicas"] == 4
    assert out["per_tenant_quota"] == 8
    # untouched knobs keep their defaults
    assert out["max_batch_size"] == cfg.SERVING_DEFAULTS["max_batch_size"]


def test_serving_config_refuses_unknown_keys():
    """The serving block carries the same unknown-key-refusal contract as
    training.guard: a typo'd knob fails loudly with a did-you-mean."""
    with pytest.raises(ValueError, match="max_batch_szie.*did you mean.*max_batch_size"):
        cfg.serving_config({"serving": {"max_batch_szie": 16}})
    with pytest.raises(ValueError, match="unknown serving key"):
        cfg.serving_config({"serving": {"zzz_not_a_knob": 1}})


def test_decode_config_disarmed_by_default():
    serving = cfg.serving_config({})
    assert serving["decode"] is None
    assert cfg.decode_config(serving) is None
    assert cfg.decode_config({"decode": False}) is None


def test_decode_config_true_and_merge():
    assert cfg.decode_config({"decode": True}) == cfg.DECODE_DEFAULTS
    out = cfg.decode_config(
        {"decode": {"max_slots": 16, "stop_token": 3, "temperature": 0.7}}
    )
    assert out["max_slots"] == 16 and out["stop_token"] == 3
    assert out["temperature"] == 0.7
    # untouched knobs keep their defaults
    assert out["kv_block_size"] == cfg.DECODE_DEFAULTS["kv_block_size"]
    # the serving loader carries the block through intact
    serving = cfg.serving_config({"serving": {"decode": {"max_slots": 2}}})
    assert cfg.decode_config(serving)["max_slots"] == 2


def test_decode_config_refuses_unknown_keys_and_bad_type():
    """serving.decode rides the same unknown-key-refusal contract as every
    other block: a typo'd knob fails loudly with a did-you-mean."""
    with pytest.raises(ValueError, match="max_slot.*did you mean.*max_slots"):
        cfg.decode_config({"decode": {"max_slot": 4}})
    with pytest.raises(ValueError, match="unknown serving.decode key"):
        cfg.decode_config({"decode": {"zzz_not_a_knob": 1}})
    with pytest.raises(ValueError, match="mapping or bool"):
        cfg.decode_config({"decode": 7})
