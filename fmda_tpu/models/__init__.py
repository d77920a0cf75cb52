from fmda_tpu.models.attn import TemporalTransformer
from fmda_tpu.models.bigru import BiGRU, BiGRUState
from fmda_tpu.models.bilstm import BiLSTM, BiLSTMState
from fmda_tpu.models.decoder import MoEDecoder
from fmda_tpu.models.ssm import GatedSSM, SSMState


def build_model(cfg):
    """The ``ModelConfig.cell`` -> module factory used by the Trainer,
    the window-re-scan Predictor, and the backtester: four families that
    classify a float feature window (``gru``, ``lstm``, ``attn``,
    ``ssm``) and one that predicts the next token of an id sequence
    (``decoder``).  What a family is trained on — its batches, loss and
    per-step metrics — is its task (:func:`fmda_tpu.train.tasks.task_for`).
    (The streaming serving cores and the flagship entry points are
    GRU-specific and construct :class:`BiGRU` directly.)"""
    cells = {"gru": BiGRU, "lstm": BiLSTM, "attn": TemporalTransformer,
             "ssm": GatedSSM, "decoder": MoEDecoder}
    if cfg.cell not in cells:
        raise ValueError(
            f"unknown ModelConfig.cell {cfg.cell!r}; expected one of "
            f"{sorted(cells)}"
        )
    return cells[cfg.cell](cfg)


__all__ = [
    "BiGRU", "BiGRUState", "BiLSTM", "BiLSTMState",
    "GatedSSM", "MoEDecoder", "SSMState", "TemporalTransformer",
    "build_model",
]
