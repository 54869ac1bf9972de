"""Model zoo: all assigned architecture families, pure-functional JAX."""

from repro.models.model import Model, build_model, default_qstate, init_params, qstate_from_calibrator

__all__ = ["Model", "build_model", "default_qstate", "init_params", "qstate_from_calibrator"]
