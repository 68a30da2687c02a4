"""paddle.audio analog — audio feature extraction.

Reference: python/paddle/audio/ (features/layers.py: Spectrogram,
MelSpectrogram, LogMelSpectrogram, MFCC; functional.py: hz_to_mel,
mel_to_hz, compute_fbank_matrix, create_dct, power_to_db). Built on
paddle_tpu.signal.stft.
"""
from . import backends, datasets, functional  # noqa: F401
from .backends import info, load, save  # noqa: F401
from .features import (LogMelSpectrogram, MFCC,  # noqa: F401
                       MelSpectrogram, Spectrogram)
