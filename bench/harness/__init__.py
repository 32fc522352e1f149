"""The benchmark's harness: discovery, traffic, window arithmetic, trace
reduction, operation and byte counts, peaks, and the plain references."""


def fuser_shape(cfg: dict) -> dict:
    """The GEN-FUSER's sizes from a configuration file."""
    return {"d_model": cfg["fuser_d_model"], "num_heads": cfg["fuser_num_heads"],
            "num_kv_heads": cfg["fuser_num_kv_heads"], "head_dim": cfg["fuser_head_dim"],
            "d_ff": cfg["fuser_d_ff"], "enc_layers": cfg["fuser_enc_layers"],
            "dec_layers": cfg["fuser_dec_layers"], "enc_positions": cfg["fuser_enc_positions"],
            "vocab_size": cfg["fuser_vocab_size"],
            "tied_head": cfg["fuser_tie_word_embeddings"]}


def predictor_shape(cfg: dict) -> dict:
    """The quality predictor's sizes from a configuration file."""
    return {"d_model": cfg["predictor_d_model"], "num_heads": cfg["predictor_num_heads"],
            "head_dim": cfg["predictor_head_dim"], "d_ff": cfg["predictor_d_ff"],
            "layers": cfg["predictor_layers"], "vocab_size": cfg["predictor_vocab_size"]}
