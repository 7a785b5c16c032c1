"""The architecture family a configuration names: its weights and costs
are those of the code it was moved from, and a family with a file missing
fails before any weights are made."""
import hashlib
import json
import shutil

import numpy as np
import pytest

from conftest import BENCH, reduced_arch
from small import small
from chipbench import discover, weights

SEED = 1234567890123

# SHA-256 of the bytes of every tensor `weights.make` gave for the reduced
# configurations (conftest.REDUCED, 256 vocabulary rows) at SEED, and the
# costs of the full-width configuration files, computed on the CPU on the
# commit before the dense code moved into the `dense_gqa` family (from
# `weights.shapes` and `costs.py`).
WEIGHT_SHA256 = {
    "qwen3-1.7b": {
        "attn_norm":
            "259b14949fbb97678921d642761b73c7db158b65ccc44385375a8b42dbe6e2a3",
        "embed":
            "8cac544168c79c619ac0b96ece8b27105c56c34fb7056b9db4280bee59ae2bba",
        "final_norm":
            "924a9cf26077a3849ea7ce82aa1d8aa6765dba1efa802465d7a80685724d45ad",
        "k_norm":
            "c4e3a5937739798dd33dc8143ad101c6e9c4a47fa3ff23a22ceb20c46eab1ecb",
        "mlp_norm":
            "30f5c39d2e6a34840defd125d2c89568a1bb2aff480e0697fe5707716a7073b1",
        "q_norm":
            "540699cb475b7d56c39ccdf77071bf5eaf53b0066717f54dc9c4ddfb4a6a7356",
        "w_down":
            "d2c3cac87c7428f88320a6fb81c97ab26145e3c8e4ed1bce6c9d1dacd0f332e0",
        "w_gate":
            "da803f4b4945075b9d2e156d6ef1d8f238603c75d53c2317c04e2038e9f38370",
        "w_up":
            "93fe9667c330f659504116ca7985ced2366ed0b3a80854869146b57ac80746c0",
        "wk":
            "9e8ee64098d23d420496698b2f155858b1a9e2a026dbd537af766d271f86ff7a",
        "wo":
            "40f348b8d3795c7260c240026b3c8228ee5357dac568cd365faa52ec332c7cd5",
        "wq":
            "d87af835ef29cba5074c6d9d9f5234ff10435bb6c0f3f71a79e512e9ae2abc18",
        "wv":
            "69cdcc1aca9fa5a95eca2db06f806e2b9f9e5b3045a97f72266563ed189c8e85",
    },
    "qwen2.5-32b": {
        "attn_norm":
            "259b14949fbb97678921d642761b73c7db158b65ccc44385375a8b42dbe6e2a3",
        "bk":
            "b2f3bad2c074e26b17aa3f62556f4b41cc8a8b828c7acffd8011189c29134893",
        "bq":
            "9305554d127c45c4577e773a1d3f7af3263cbc6835fee2937f6f0f528c7c2ded",
        "bv":
            "95ecb68408358293569319f67700952483b5d18b044f84e86238ff50ae9c682b",
        "embed":
            "2d8d8a7c9f2ce74c4e30dc42c301e7dc42ef595e955089b8279b2174d82c9fed",
        "final_norm":
            "4ee55674539660898a84ce470f1ea46cd4068ea813725dcb63bc47c6d22196d9",
        "lm_head":
            "87debc8714e59f177a79b27b5033514047a68731ccf14f9b020868410a7532df",
        "mlp_norm":
            "f488e6e562e07ad5b2c39fbb84e45c093f3b33b32e6007262d2e3f148ae86513",
        "w_down":
            "d8c72306fecde83d0cc047b6293deb0a2fe6931a6e00a13fff2483d20ca25669",
        "w_gate":
            "8297a20fb7e4aca98ff076f66f03b0bb8f15b8a002eec901c72ad988747450cb",
        "w_up":
            "7e4ecd67a7a1242a40d319aac342acf84d84d66e8f10d71532ae807ecc298166",
        "wk":
            "70e289d17b01bd129e6525067bda893a1a2a545e51ca4fbf679bd0969004ffea",
        "wo":
            "c4e59e4ba725a58e9c2651c18a8614475be858034be55ee0307c98fcc45feee9",
        "wq":
            "ff1c86bc29dc644d3d8c02f7d8d33fb4ea5b4362576e20ea3296214dfc66bc22",
        "wv":
            "d4cd94547d64d3cba8776b0c0d62157a9cad8bfa45cf487efc74a834cf5520af",
    },
}
COSTS = {  # prompt_flops(arch, 320), decode_flops(arch, 1000),
           # sweep_work(arch, 1000)
    "qwen3-1.7b": (914346213376.0, 3670507520.0, (229376000.0, 114688000.0)),
    "qwen2.5-32b": (2506421698560.0, 9522544640.0, (163840000.0, 32768000.0)),
}


@pytest.mark.parametrize("name", sorted(WEIGHT_SHA256))
def test_dense_weights_are_bit_identical_to_the_parents(name):
    arch = reduced_arch(name)
    assert discover.family(arch).name == "dense_gqa"
    w = weights.make(arch, SEED, 256)
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
           for k, v in w.items()}
    assert got == WEIGHT_SHA256[name]


@pytest.mark.parametrize("name", sorted(COSTS))
def test_dense_costs_are_the_parents(name):
    arch = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    ref = discover.family(arch).reference
    assert (ref.prompt_flops(arch, 320), ref.decode_flops(arch, 1000),
            ref.sweep_work(arch, 1000)) == COSTS[name]


def test_an_unknown_family_fails_before_any_weights_are_made(monkeypatch):
    from chipbench import run as R
    made = []
    monkeypatch.setattr(weights, "make", lambda *a, **k: made.append(a))
    arch, cell, mix = small("qwen3-1.7b.chat")
    arch["family"] = "no_such_family"
    with pytest.raises(FileNotFoundError) as e:
        R.run("qwen3-1.7b.chat", 1, 1.0, False, arch=arch, cell=cell,
              mix=mix, require_tpu=False)
    assert str(BENCH / "reference" / "no_such_family.py") in str(e.value)
    assert not made


def test_a_family_without_its_adapter_names_the_missing_file(tmp_path):
    base = tmp_path / "chipbench"
    (base / "reference").mkdir(parents=True)
    shutil.copy(BENCH / "reference" / "dense_gqa.py",
                base / "reference" / "half.py")
    with pytest.raises(FileNotFoundError) as e:
        discover.family({"family": "half"}, base)
    assert str(base / "adapters" / "half.py") in str(e.value)
