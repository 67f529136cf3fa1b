"""paddle.audio tests (reference: ``test/legacy_test/test_audio_functions.py``
† pattern — mel scale math, filterbanks, windows, feature layers against
scipy/closed-form oracles)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.audio import functional as AF

scipy_signal = pytest.importorskip("scipy.signal")


class TestScales:
    def test_hz_mel_roundtrip(self):
        f = np.array([0.0, 440.0, 1000.0, 4000.0], np.float32)
        mel = AF.hz_to_mel(paddle.to_tensor(f))
        back = AF.mel_to_hz(mel)
        np.testing.assert_allclose(back.numpy(), f, rtol=1e-4, atol=1e-2)

    def test_known_values_slaney(self):
        # the slaney scale is linear below 1 kHz: 1000 Hz == 15 mel
        assert abs(AF.hz_to_mel(1000.0) - 15.0) < 1e-4
        assert abs(AF.mel_to_hz(15.0) - 1000.0) < 1e-2

    def test_htk(self):
        assert abs(AF.hz_to_mel(1000.0, htk=True)
                   - 2595.0 * np.log10(1.0 + 1000.0 / 700.0)) < 1e-2

    def test_fft_frequencies(self):
        got = AF.fft_frequencies(8000, 256).numpy()
        np.testing.assert_allclose(got, np.fft.rfftfreq(256, 1 / 8000.0),
                                   rtol=1e-6)


class TestFilterbankDct:
    def test_fbank_shape_and_coverage(self):
        fb = AF.compute_fbank_matrix(8000, 256, n_mels=32).numpy()
        assert fb.shape == (32, 129)
        assert (fb >= 0).all()
        # every filter has some support; interior bins are covered
        assert (fb.sum(axis=1) > 0).all()

    def test_dct_ortho(self):
        d = AF.create_dct(13, 32, norm="ortho").numpy()  # [n_mels, n_mfcc]
        gram = d.T @ d
        np.testing.assert_allclose(gram, np.eye(13), atol=1e-5)

    def test_power_to_db(self):
        x = paddle.to_tensor(np.array([1.0, 0.1, 0.01], np.float32))
        db = AF.power_to_db(x, top_db=None).numpy()
        np.testing.assert_allclose(db, [0.0, -10.0, -20.0], atol=1e-4)
        capped = AF.power_to_db(x, top_db=15.0).numpy()
        np.testing.assert_allclose(capped, [0.0, -10.0, -15.0], atol=1e-4)


class TestWindows:
    @pytest.mark.parametrize("name", ["hann", "hamming", "blackman",
                                      "bartlett"])
    def test_matches_scipy(self, name):
        ours = AF.get_window(name, 64).numpy()
        ref = scipy_signal.get_window(name, 64, fftbins=True)
        np.testing.assert_allclose(ours, ref, atol=1e-5)

    def test_kaiser(self):
        ours = AF.get_window(("kaiser", 8.0), 64).numpy()
        ref = scipy_signal.get_window(("kaiser", 8.0), 64, fftbins=True)
        np.testing.assert_allclose(ours, ref, atol=1e-4)


class TestAudioFeatures:
    def _tone(self, freq=440.0, sr=8000, n=4000):
        t = np.arange(n) / sr
        return np.sin(2 * np.pi * freq * t).astype(np.float32)[None]

    def test_spectrogram_peak_at_tone(self):
        sr, f0 = 8000, 1000.0
        from paddle_tpu.audio.features import Spectrogram
        spec = Spectrogram(n_fft=256)(paddle.to_tensor(self._tone(f0, sr)))
        s = spec.numpy()[0]
        peak_bin = s.mean(axis=-1).argmax()
        np.testing.assert_allclose(peak_bin * sr / 256, f0, atol=sr / 256)

    def test_mel_and_mfcc_shapes_finite(self):
        from paddle_tpu.audio.features import (LogMelSpectrogram, MFCC,
                                               MelSpectrogram)
        x = paddle.to_tensor(self._tone())
        mel = MelSpectrogram(sr=8000, n_fft=256, n_mels=32)(x)
        assert mel.shape[1] == 32 and np.isfinite(mel.numpy()).all()
        lm = LogMelSpectrogram(sr=8000, n_fft=256, n_mels=32, top_db=80.0)(x)
        assert np.isfinite(lm.numpy()).all()
        mfcc = MFCC(sr=8000, n_mfcc=13, n_fft=256, n_mels=32)(x)
        assert mfcc.shape[1] == 13 and np.isfinite(mfcc.numpy()).all()

    def test_mel_energy_concentrates_at_tone(self):
        from paddle_tpu.audio.features import MelSpectrogram
        sr = 8000
        m = MelSpectrogram(sr=sr, n_fft=512, n_mels=40, f_min=0.0)
        lo = m(paddle.to_tensor(self._tone(300.0, sr))).numpy()[0].mean(-1)
        hi = m(paddle.to_tensor(self._tone(3000.0, sr))).numpy()[0].mean(-1)
        assert lo.argmax() < hi.argmax()
