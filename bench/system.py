"""The program's settings, from a configuration file.

Every setting has to be a field of the program's ``EngineConfig`` or
``PartitionConfig``: a setting the program no longer has is returned as
dropped, and the run refuses it, since the cell would then run another
path than its configuration states.
"""
from __future__ import annotations

import dataclasses


def _known(cls, settings: dict):
    names = {f.name for f in dataclasses.fields(cls)}
    kept = {k: v for k, v in settings.items() if k in names}
    dropped = sorted(set(settings) - names)
    return cls(**kept), dropped


def engine_config(settings: dict):
    """(EngineConfig, dropped field names)."""
    from repro.core.engine import EngineConfig
    return _known(EngineConfig, settings)


def partition_config(settings: dict):
    """(PartitionConfig, dropped field names)."""
    from repro.core.partition import PartitionConfig
    return _known(PartitionConfig, settings)
