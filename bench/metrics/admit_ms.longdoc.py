"""The reader of ``admit_ms.chat``, under a name of its own for the cells
judged on ``tokens_per_s``, the metric it moves there."""
import registry


def read(run):
    return registry.metric_reader("admit_ms.chat")(run)
