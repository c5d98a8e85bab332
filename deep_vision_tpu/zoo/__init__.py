"""Experiment zoo: registers a TrainConfig per model, replacing the
reference's per-directory ``training_config`` dicts."""

import deep_vision_tpu.zoo.centernet  # noqa: F401
import deep_vision_tpu.zoo.classifiers  # noqa: F401
import deep_vision_tpu.zoo.detection  # noqa: F401
import deep_vision_tpu.zoo.gan  # noqa: F401
import deep_vision_tpu.zoo.language  # noqa: F401
import deep_vision_tpu.zoo.lenet  # noqa: F401
import deep_vision_tpu.zoo.pose  # noqa: F401
import deep_vision_tpu.zoo.resnet  # noqa: F401
