from .hw_model import HARDWARE, HardwareModel, hardware_for
from .ops import (flash_attention, masked_select, nonzero_pad, rmsnorm,
                  topk_dynamic, unique_bounded)
from .variants import (KernelSelection, KernelVariant, default_variant,
                       registered_kernels, select_kernels, variants_for)

__all__ = ["flash_attention", "rmsnorm", "nonzero_pad", "masked_select",
           "topk_dynamic", "unique_bounded", "HardwareModel", "HARDWARE",
           "hardware_for",
           "KernelVariant", "KernelSelection", "variants_for",
           "default_variant", "registered_kernels", "select_kernels"]
