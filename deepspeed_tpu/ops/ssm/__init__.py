from .selective_scan import (selective_scan, selective_scan_xla, selective_step,
                             selective_step_xla)
from .step import ssm_step, ssm_step_xla

__all__ = ["selective_scan", "selective_scan_xla", "selective_step",
           "selective_step_xla", "ssm_step", "ssm_step_xla"]
