"""Model FLOP/s utilisation: required flops per token (the configuration
names the function) x tokens/s/chip over the chip's bf16 peak."""
from benchmark.flops import mfu_pct
from benchmark.model_api import load


def read(observed):
    rate = observed.get("tokens_per_s_chip")
    if rate is None:
        return None
    per_token = load(observed["config"]["model"]["flops_per_token"])(
        observed["sizes"], observed["traffic"]["seq"])
    return mfu_pct(per_token, rate, observed["device_kind"])
