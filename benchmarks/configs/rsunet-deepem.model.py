"""--model-path file of the rsunet-deepem configuration: the program's own
RSUNet module at the widths of upstream chunkflow's
examples/inference/universal_pytorch.py (model='rsunet',
width=[16, 32, 64, 128]), through the documented user-model route
(examples/inference/custom_flax_model.py) that `inference` and `serve`
both take. float32 compute, the command line's default."""
from chunkflow_tpu.models import rsunet


def create_model(num_input_channels, num_output_channels):
    return rsunet.RSUNet(in_channels=num_input_channels,
                         out_channels=num_output_channels,
                         width=(16, 32, 64, 128))
