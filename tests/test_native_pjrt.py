"""C++ PJRT resources/mdarray layer, driven against the in-tree mock
plugin (the same dlopen + GetPjrtApi path production uses for
libtpu). Reference roles: handle_t
(core/handle.hpp:54-316) and mdarray (core/mdarray.hpp:125)."""

import os

import numpy as np
import pytest

from raft_tpu.core import pjrt_native


pytestmark = pytest.mark.skipif(
    not pjrt_native.available()
    or not os.path.exists(pjrt_native.mock_plugin_path()),
    reason="PJRT native layer or mock plugin not built")


@pytest.fixture()
def res():
    r = pjrt_native.NativeResources(pjrt_native.mock_plugin_path())
    yield r
    r.close()


class TestNativeResources:
    def test_platform_and_devices(self, res):
        assert res.platform_name == "mockcpu"
        assert res.device_count() == 2
        assert res.device_ids() == [0, 1]
        assert res.process_index == 0
        major, minor = res.api_version
        assert major >= 0 and minor > 0

    def test_bad_plugin_path_is_clean_error(self):
        with pytest.raises(Exception, match="dlopen"):
            pjrt_native.NativeResources("/nonexistent/libnope.so")

    def test_create_options_pass_through(self):
        """Client create-options (PJRT_NamedValues — required by real
        plugins) flow through the C ABI; the
        mock plugin accepts-and-ignores them."""
        opts = {"topology": "v5e:1x1x1", "n_slices": 1,
                "flag": True, "timeout_frac": 1.5}
        with pjrt_native.NativeResources(
                pjrt_native.mock_plugin_path(), options=opts) as r:
            assert r.device_count() == 2

    def test_option_name_reserved_chars_rejected(self):
        from raft_tpu.core.error import LogicError
        with pytest.raises(LogicError):
            pjrt_native.NativeResources(
                pjrt_native.mock_plugin_path(),
                options={"bad;name": 1})

    def test_encode_create_options(self):
        spec = pjrt_native.encode_create_options(
            {"a": 1, "b": "x", "c": True, "d": 2.5})
        assert spec == "a=i:1;b=s:x;c=b:1;d=f:2.5"

    def test_context_manager_closes(self):
        with pjrt_native.NativeResources(
                pjrt_native.mock_plugin_path()) as r:
            assert r.device_count() == 2
        # closed: calls now fail cleanly
        assert r.device_count() == -1


class TestNativeMdarray:
    @pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32,
                                       np.int64, np.uint8])
    def test_roundtrip(self, res, dtype):
        rng = np.random.default_rng(0)
        a = (rng.random((7, 5)) * 100).astype(dtype)
        m = res.device_put(a)
        assert m.shape == (7, 5)
        assert m.dtype == np.dtype(dtype)
        np.testing.assert_array_equal(m.to_numpy(), a)
        m.destroy()

    def test_second_device(self, res):
        a = np.arange(6, dtype=np.float32).reshape(2, 3)
        m = res.device_put(a, device_index=1)
        np.testing.assert_array_equal(m.to_numpy(), a)

    def test_sync_and_ready(self, res):
        m = res.device_put(np.ones((4,), np.float32))
        assert m.ready()  # mock device is synchronous
        m.sync()          # stream_syncer role: must not raise

    def test_bad_device_index(self, res):
        with pytest.raises(Exception, match="device index"):
            res.device_put(np.ones((2,), np.float32), device_index=9)

    def test_destroy_then_use_fails_cleanly(self, res):
        m = res.device_put(np.ones((2,), np.float32))
        m.destroy()
        with pytest.raises(Exception):
            _ = m.shape

    def test_resources_close_orphans_buffers(self):
        r = pjrt_native.NativeResources(pjrt_native.mock_plugin_path())
        m = r.device_put(np.ones((3,), np.float32))
        r.close()  # destroys the client AND its buffers
        with pytest.raises(Exception):
            _ = m.shape
        m.destroy()  # already gone: must be a no-op, not a crash
