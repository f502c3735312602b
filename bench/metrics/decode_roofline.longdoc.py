"""The reader of ``decode_roofline.serve``, under a name of its own for the
cells judged on ``tokens_per_s``, the metric it moves there."""
import registry


def read(run):
    return registry.metric_reader("decode_roofline.serve")(run)
