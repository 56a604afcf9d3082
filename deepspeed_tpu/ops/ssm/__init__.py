from .step import ssm_step, ssm_step_xla

__all__ = ["ssm_step", "ssm_step_xla"]
