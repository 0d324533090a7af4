"""Networks the mapper maps, by name: the paper's CNN zoo and the lowered
LM prefill chains (``LM_PREFILL``: config name and prompt length)."""
from .layer import Layer, Workload
from .cnn_zoo import (CNN_ZOO, vgg16, resnet18, resnet50, mobilenet_v2,
                      mnasnet_b1, tiny_cnn)

__all__ = ["Layer", "Workload", "CNN_ZOO", "LM_PREFILL", "get_workload",
           "vgg16", "resnet18", "resnet50", "mobilenet_v2", "mnasnet_b1",
           "tiny_cnn"]

LM_PREFILL = {f"deepseek_v3_prefill_{k}k": ("deepseek_v3", k * 1024)
              for k in (4, 16, 32)}


def get_workload(name: str, batch: int = 64) -> Workload:
    if name in CNN_ZOO:
        return CNN_ZOO[name](batch)
    if name in LM_PREFILL:
        from ..configs import get_config
        from .lm_workloads import lm_workload
        arch, seq = LM_PREFILL[name]
        return lm_workload(get_config(arch), seq_len=seq, batch=batch,
                           mode="prefill")
    raise KeyError(f"unknown workload {name!r}; have "
                   f"{sorted(CNN_ZOO) + sorted(LM_PREFILL)}")
