"""The system under test: the program's serving engine, built the way a
user builds it (``launch.specs.make_plan`` -> parameters on the mesh ->
``serving.ServingEngine``), from a configuration file and a cell's engine
settings, with the benchmark's own weights from the seed."""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import weights


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with every size the file states."""
    from repro.configs import get_config
    heads = config["num_attention_heads"]
    return get_config(
        config["program_arch"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["hidden_size"] // heads,
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        qkv_bias=bool(config["qkv_bias"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        hnn_mode=config["hnn_mode"],
        codec=config["codec"])


def build(config: dict, engine: dict, seed: int):
    """(engine, params) for one run: a ``1 x tp`` mesh, the benchmark's
    weights made on it from ``seed``, and the engine over them."""
    from repro.configs.base import ShapeCell
    from repro.launch import specs, train
    from repro.launch.mesh import make_mesh
    from repro.serving import EngineConfig, ServingEngine

    cfg = model_config(config)
    mesh = make_mesh((1, int(config["tp"])), ("data", "model"))
    plan = specs.make_plan(cfg, ShapeCell("serve_decode", engine["max_seq"],
                                          engine["num_slots"], "decode"),
                           mesh)
    structs, pspecs = train.abstract_sharded_params(cfg, plan)
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
    params = weights.make_params(structs, shardings, seed,
                                  config.get("weights"))
    ecfg = EngineConfig(num_slots=engine["num_slots"],
                        max_seq=engine["max_seq"],
                        prefill_len=engine["prefill_len"],
                        page_size=engine["page_size"],
                        num_pages=engine["num_pages"],
                        async_depth=engine["async_depth"])
    return ServingEngine(cfg, mesh, params, ecfg), params
