"""Builds the program's ERNIE masked-LM from a configuration file's sizes."""


def build(cfg: dict):
    from paddle_tpu.models import ErnieForMaskedLM, ErnieModel

    return ErnieForMaskedLM(ErnieModel(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"], hidden_act=cfg["hidden_act"],
        hidden_dropout_prob=cfg["hidden_dropout_prob"],
        attention_probs_dropout_prob=cfg["attention_probs_dropout_prob"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        initializer_range=cfg["initializer_range"], pad_token_id=cfg["pad_token_id"],
    ))
