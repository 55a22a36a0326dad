"""The expert layer: dropless top-k routing and one chip's share of the
routed experts as a grouped matrix product (`dropless.py`).  The blocks
that use it bring their own config and model class
(``models/latent_moe.py`` serves, ``models/cca_moe.py`` trains).
"""
from . import dropless

__all__ = ["dropless"]
