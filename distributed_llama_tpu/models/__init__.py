from .spec import ArchType, HiddenAct, LayerKind, ModelSpec

__all__ = ["ArchType", "HiddenAct", "LayerKind", "ModelSpec"]
