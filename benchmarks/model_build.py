"""From a cell's files to the program's own configuration objects. The model's
configuration comes from the family's file (``harness.family``), the engine's
settings from the mix."""

from __future__ import annotations

import dataclasses

from benchmarks import harness


def llm_config(c: dict, mix: dict, seed: int, num_tpus: int = 1):
    """The ``LLMConfig`` one replica of a serve cell runs with: every key of
    the mix's ``engine`` is a field of ``LLMConfig`` and is passed as it
    stands (a list as a tuple); a key that ends in ``_why`` is prose. So a mix
    that turns a setting of the engine on is a data file."""
    from ray_tpu.llm.config import LLMConfig

    fields = {f.name for f in dataclasses.fields(LLMConfig)}
    ours = {"model_id", "model_config", "placement", "seed"}
    engine = {k: v for k, v in mix["engine"].items() if not k.endswith("_why")}
    unknown = sorted(set(engine) - (fields - ours))
    if unknown:
        raise SystemExit(f"the mix's engine names {unknown}, which LLMConfig does not take from a mix")
    return LLMConfig(
        model_id=c["name"],
        model_config=harness.family(c).model_config(c, mix),
        placement={"num_tpus": num_tpus, "num_cpus": 1},
        seed=seed,
        **{k: tuple(v) if isinstance(v, list) else v for k, v in engine.items()},
    )
