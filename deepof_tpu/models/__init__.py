from .common import count_params, bilinear_kernel_init, load_vgg16_npz  # noqa: F401
from .flownet_s import FlowNetS  # noqa: F401
from .vgg16_flow import VGG16Flow, VGG16Trunk  # noqa: F401
from .inception_v3_flow import InceptionV3Flow  # noqa: F401
from .flownet_c import FlowNetC  # noqa: F401
from .flownet2 import FlowNetCS  # noqa: F401
from .two_stream import UCF101Spatial, STSingle, STBaseline  # noqa: F401
from .lm import LatentMoELM  # noqa: F401
from .registry import build_model, MODELS  # noqa: F401
