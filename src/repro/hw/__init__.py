from repro.hw.tpu import (TpuTarget, get_target, target_for_device, KiB,
                          MiB, GiB)

__all__ = ["TpuTarget", "get_target", "target_for_device", "KiB", "MiB",
           "GiB"]
