"""mxnet_tpu.chip: the package's peaks table (keyed by device_kind, with
source).  An unknown device is an error."""
import pytest

from mxnet_tpu import chip
from mxnet_tpu.base import MXNetError


def test_v5e_row():
    p = chip.peaks("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == \
        (197e12, 819e9, 16e9)
    assert p.source


@pytest.mark.parametrize("kind", ["cpu", "", "TPU v5", "mystery accelerator"])
def test_unknown_kind_raises(kind):
    with pytest.raises(MXNetError, match="no published peaks"):
        chip.peaks(kind)


def test_default_kind_is_device_zero():
    # the tier-1 host is a CPU: the default lookup must raise, not guess
    assert chip.device_kind() == "cpu"
    with pytest.raises(MXNetError, match="no published peaks"):
        chip.peaks()
