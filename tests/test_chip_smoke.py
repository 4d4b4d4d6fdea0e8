"""``chip_smoke.py`` rehearsed on the CPU at smoke width.

The chip smoke drives qwen3-1.7b at full width on a TPU; here its phases
run on the CPU backend (kernels in interpret mode) at the smoke config,
so a change that breaks the script's control flow, arguments or checks
fails in tier-1 instead of on the chip.  The TPU-only parts — the device
check and the Mosaic custom call in the lowered decode step — are
exercised only on the chip.
"""

import dataclasses
import os
import sys

import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import model as model_lib
from repro.sharding import partitioning as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

TRAFFIC = chip_smoke.Traffic(system_len=16, suffix_lens=(48, 16, 32, 16),
                             max_new=4, slots=2, max_len=128)


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config(chip_smoke.ARCH)


@pytest.fixture(scope="module")
def params(cfg):
    return P.materialize(model_lib.specs(cfg, 1),
                         jax.random.PRNGKey(chip_smoke.SEED))


@pytest.fixture(scope="module")
def ref_first(params, cfg):
    return chip_smoke.phase_reference(params, cfg, TRAFFIC)


def test_phase_a(params, cfg, ref_first):
    chip_smoke.phase_a(params, cfg, TRAFFIC, ref_first)


def test_phase_b(params, cfg, ref_first):
    eng = chip_smoke.phase_b(params, cfg, TRAFFIC, ref_first)
    assert eng.cache_format == "paged_int4_bp_fused"


def test_phase_c(cfg):
    chip_smoke.phase_c(cfg, batches=(1, 8),
                       attention_cases=((32, 1), (32, 32)))


def test_four_chip_training(cfg):
    chip_smoke.four_chips(dataclasses.replace(cfg, n_layers=4), steps=2,
                          seq_len=16, global_batch=4)


def test_exits_nonzero_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
