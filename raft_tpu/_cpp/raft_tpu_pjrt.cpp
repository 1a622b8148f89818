// C++ resources + mdarray layer over the PJRT C API.
//
// The reference's host-side runtime core is C++: `handle_t` owns the
// device context and vendor handles (cpp/include/raft/core/handle.hpp:
// 54-316) and `mdarray` owns device storage with dtype/extents
// (core/mdarray.hpp:125). SURVEY.md §2's language plan asks for the same
// split on TPU: a C++ resource/container layer bound to the device
// runtime through the *stable C ABI* the TPU stack actually exposes —
// the PJRT C API (GetPjrtApi from a plugin .so such as libtpu).
//
//   rtp_resources_*  ≈ handle_t     — dlopen a PJRT plugin, create the
//                                     client, enumerate addressable
//                                     devices (stream/vendor-handle
//                                     slots have no TPU analogue; XLA
//                                     orders execution).
//   rtp_buffer_*     ≈ mdarray      — owning device buffers with
//                                     dtype + extents; host round-trips
//                                     via BufferFromHostBuffer /
//                                     ToHostBuffer.
//   rtp_buffer_sync  ≈ stream_syncer/interruptible::synchronize — block
//                                     on the buffer's ready event.
//
// This is the *runtime* layer only: compilation/execution stays with
// XLA through JAX (SURVEY.md §2.10 note — on TPU the natural runtime
// API is Python/JAX; the C++ layer owns process-lifetime resources and
// containers, exactly the split the reference draws between handle/
// mdarray and algorithm code).
//
// Exposed to Python via ctypes (raft_tpu/core/pjrt_native.py); tested
// against the in-tree mock plugin (mock_pjrt_plugin.cpp) on CPU and
// loadable against the real plugin on TPU hosts.

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <optional>
#include <string>
#include <vector>

#include <ctime>
#include <dlfcn.h>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct Resources {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  std::vector<PJRT_Device*> devices;  // addressable
};

struct Buffer {
  int64_t res_id = 0;
  PJRT_Buffer* buf = nullptr;
};

std::mutex g_mu;
std::map<int64_t, Resources> g_res;
std::map<int64_t, Buffer> g_buf;
// awaits in flight per resources id: rtp_resources_destroy must not
// free the client / dlclose while another thread blocks in an await
// outside g_mu (the lock convention: slow device work never holds the
// registry lock). Destroy marks the id dying first so no NEW await can
// start, then drains the count.
std::map<int64_t, int> g_inflight;
std::map<int64_t, bool> g_dying;
int64_t g_next_id = 1;

bool is_dying(int64_t id) {  // caller holds g_mu
  auto it = g_dying.find(id);
  return it != g_dying.end() && it->second;
}

struct InflightGuard {
  int64_t id;
  explicit InflightGuard(int64_t res_id) : id(res_id) {
    // caller holds g_mu
    ++g_inflight[id];
  }
  void release() {
    if (!id) return;
    std::lock_guard<std::mutex> lk(g_mu);
    if (--g_inflight[id] <= 0) g_inflight.erase(id);
    id = 0;
  }
  ~InflightGuard() { release(); }
};

void set_err(char* err, int errlen, const std::string& msg) {
  if (err && errlen > 0) {
    std::snprintf(err, static_cast<size_t>(errlen), "%s", msg.c_str());
  }
}

// Extract + free a PJRT_Error; returns true if there was an error.
bool take_error(const PJRT_Api* api, PJRT_Error* e, std::string* out) {
  if (!e) return false;
  PJRT_Error_Message_Args m;
  std::memset(&m, 0, sizeof m);
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = e;
  api->PJRT_Error_Message(&m);
  if (out) out->assign(m.message, m.message_size);
  PJRT_Error_Destroy_Args d;
  std::memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = e;
  api->PJRT_Error_Destroy(&d);
  return true;
}

// Await + destroy an event; returns error message via *out (empty = ok).
bool await_event(const PJRT_Api* api, PJRT_Event* ev, std::string* out) {
  if (!ev) return false;
  PJRT_Event_Await_Args a;
  std::memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  a.event = ev;
  PJRT_Error* e = api->PJRT_Event_Await(&a);
  bool bad = take_error(api, e, out);
  PJRT_Event_Destroy_Args d;
  std::memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = ev;
  take_error(api, api->PJRT_Event_Destroy(&d), nullptr);
  return bad;
}

Resources* find_res(int64_t id) {
  auto it = g_res.find(id);
  return it == g_res.end() ? nullptr : &it->second;
}

Buffer* find_buf(int64_t id) {
  auto it = g_buf.find(id);
  return it == g_buf.end() ? nullptr : &it->second;
}

}  // namespace

// Parse a flat create-options spec into PJRT_NamedValues. Grammar:
// entries split on ';', each "name=T:value" with T one of s (string),
// i (int64), f (float), b (bool 0/1). Real plugins (libtpu) may
// require options at PJRT_Client_Create (their registration contract);
// the flat spec keeps the ctypes ABI a single string. String storage
// must outlive the call: the caller keeps `storage` alive.
bool parse_create_options(const std::string& spec,
                          std::vector<std::string>* storage,
                          std::vector<PJRT_NamedValue>* out,
                          std::string* bad) {
  size_t pos = 0;
  // two passes so `storage` never reallocates while NamedValues point
  // into it: collect pieces first, then build the value structs
  struct Piece { std::string name; char ty; std::string val; };
  std::vector<Piece> pieces;
  while (pos < spec.size()) {
    size_t end = spec.find(';', pos);
    if (end == std::string::npos) end = spec.size();
    std::string entry = spec.substr(pos, end - pos);
    pos = end + 1;
    if (entry.empty()) continue;
    size_t eq = entry.find('=');
    if (eq == std::string::npos || eq + 2 >= entry.size() ||
        entry[eq + 2] != ':') {
      *bad = "bad option entry (want name=T:value): " + entry;
      return false;
    }
    pieces.push_back({entry.substr(0, eq), entry[eq + 1],
                      entry.substr(eq + 3)});
  }
  storage->reserve(storage->size() + 2 * pieces.size());
  for (const auto& p : pieces) {
    storage->push_back(p.name);
    const std::string& name_ref = storage->back();
    PJRT_NamedValue nv;
    std::memset(&nv, 0, sizeof nv);
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = name_ref.c_str();
    nv.name_size = name_ref.size();
    nv.value_size = 1;
    switch (p.ty) {
      case 's': {
        storage->push_back(p.val);
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = storage->back().c_str();
        nv.value_size = storage->back().size();
        break;
      }
      case 'i': {
        char* endp = nullptr;
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = std::strtoll(p.val.c_str(), &endp, 10);
        if (p.val.empty() || *endp != '\0') {
          *bad = "bad int option value in: " + p.name + "=" + p.val;
          return false;
        }
        break;
      }
      case 'f': {
        char* endp = nullptr;
        nv.type = PJRT_NamedValue_kFloat;
        nv.float_value = std::strtof(p.val.c_str(), &endp);
        if (p.val.empty() || *endp != '\0') {
          *bad = "bad float option value in: " + p.name + "=" + p.val;
          return false;
        }
        break;
      }
      case 'b':
        nv.type = PJRT_NamedValue_kBool;
        nv.bool_value = p.val != "0" && p.val != "false";
        break;
      default:
        *bad = std::string("bad option type '") + p.ty +
               "' (want s|i|f|b) in: " + p.name;
        return false;
    }
    out->push_back(nv);
  }
  return true;
}

extern "C" {

int rtp_abi_version() { return 2; }

int64_t rtp_resources_create_opts(const char* plugin_path,
                                  const char* options_spec, char* err,
                                  int errlen);

// Create: dlopen the plugin, GetPjrtApi, Plugin_Initialize,
// Client_Create (no options), enumerate addressable devices. Returns
// id > 0, or 0 with *err filled.
int64_t rtp_resources_create(const char* plugin_path, char* err,
                             int errlen) {
  return rtp_resources_create_opts(plugin_path, "", err, errlen);
}

// As rtp_resources_create, with client create-options (see
// parse_create_options for the spec grammar).
int64_t rtp_resources_create_opts(const char* plugin_path,
                                  const char* options_spec, char* err,
                                  int errlen) {
  Resources r;
  r.dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!r.dl) {
    set_err(err, errlen, std::string("dlopen: ") + dlerror());
    return 0;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(r.dl, "GetPjrtApi"));
  if (!get_api) {
    set_err(err, errlen, "plugin has no GetPjrtApi symbol");
    dlclose(r.dl);
    return 0;
  }
  r.api = get_api();
  if (!r.api) {
    set_err(err, errlen, "GetPjrtApi returned null");
    dlclose(r.dl);
    return 0;
  }
  std::string msg;
  if (r.api->PJRT_Plugin_Initialize) {
    PJRT_Plugin_Initialize_Args pi;
    std::memset(&pi, 0, sizeof pi);
    pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    if (take_error(r.api, r.api->PJRT_Plugin_Initialize(&pi), &msg)) {
      set_err(err, errlen, "Plugin_Initialize: " + msg);
      dlclose(r.dl);
      return 0;
    }
  }
  std::vector<std::string> opt_storage;
  std::vector<PJRT_NamedValue> opts;
  {
    std::string bad;
    if (!parse_create_options(options_spec ? options_spec : "",
                              &opt_storage, &opts, &bad)) {
      set_err(err, errlen, "create options: " + bad);
      dlclose(r.dl);
      return 0;
    }
  }
  PJRT_Client_Create_Args cc;
  std::memset(&cc, 0, sizeof cc);
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cc.create_options = opts.empty() ? nullptr : opts.data();
  cc.num_options = opts.size();
  if (take_error(r.api, r.api->PJRT_Client_Create(&cc), &msg)) {
    set_err(err, errlen, "Client_Create: " + msg);
    dlclose(r.dl);
    return 0;
  }
  r.client = cc.client;
  PJRT_Client_AddressableDevices_Args ad;
  std::memset(&ad, 0, sizeof ad);
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.client = r.client;
  if (take_error(r.api, r.api->PJRT_Client_AddressableDevices(&ad),
                 &msg)) {
    // fatal: a handle with no device list would only fail later with
    // misleading "bad device index" errors
    set_err(err, errlen, "AddressableDevices: " + msg);
    PJRT_Client_Destroy_Args cd;
    std::memset(&cd, 0, sizeof cd);
    cd.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    cd.client = r.client;
    take_error(r.api, r.api->PJRT_Client_Destroy(&cd), nullptr);
    dlclose(r.dl);
    return 0;
  }
  r.devices.assign(ad.addressable_devices,
                   ad.addressable_devices + ad.num_addressable_devices);
  std::lock_guard<std::mutex> lk(g_mu);
  int64_t id = g_next_id++;
  g_res[id] = r;
  return id;
}

void rtp_resources_destroy(int64_t id) {
  Resources r;
  // drain in-flight awaits first: freeing the client / dlclosing while
  // another thread blocks inside PJRT_Event_Await would use-after-free.
  // The dying mark stops new awaits from starting mid-drain.
  {
    std::lock_guard<std::mutex> lk(g_mu);
    if (g_res.find(id) == g_res.end()) return;
    g_dying[id] = true;
  }
  for (;;) {
    {
      std::lock_guard<std::mutex> lk(g_mu);
      auto inf = g_inflight.find(id);
      if (inf == g_inflight.end() || inf->second <= 0) break;
    }
    struct timespec ts {0, 1000000};  // 1 ms
    nanosleep(&ts, nullptr);
  }
  {
    std::lock_guard<std::mutex> lk(g_mu);
    auto it = g_res.find(id);
    if (it == g_res.end()) return;
    r = it->second;
    g_res.erase(it);
    // orphan any buffers still owned by this resources object
    for (auto bit = g_buf.begin(); bit != g_buf.end();) {
      if (bit->second.res_id == id) {
        PJRT_Buffer_Destroy_Args d;
        std::memset(&d, 0, sizeof d);
        d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
        d.buffer = bit->second.buf;
        take_error(r.api, r.api->PJRT_Buffer_Destroy(&d), nullptr);
        bit = g_buf.erase(bit);
      } else {
        ++bit;
      }
    }
    g_dying.erase(id);
  }
  PJRT_Client_Destroy_Args d;
  std::memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
  d.client = r.client;
  take_error(r.api, r.api->PJRT_Client_Destroy(&d), nullptr);
  if (r.dl) dlclose(r.dl);
}

int rtp_platform_name(int64_t id, char* buf, int buflen) {
  std::lock_guard<std::mutex> lk(g_mu);
  Resources* r = find_res(id);
  if (!r) return -1;
  PJRT_Client_PlatformName_Args a;
  std::memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  a.client = r->client;
  if (take_error(r->api, r->api->PJRT_Client_PlatformName(&a), nullptr))
    return -2;
  int n = static_cast<int>(a.platform_name_size);
  if (n >= buflen) n = buflen - 1;
  if (n < 0) n = 0;
  std::memcpy(buf, a.platform_name, static_cast<size_t>(n));
  buf[n] = '\0';
  return n;
}

int rtp_api_version(int64_t id, int* major, int* minor) {
  std::lock_guard<std::mutex> lk(g_mu);
  Resources* r = find_res(id);
  if (!r) return -1;
  *major = r->api->pjrt_api_version.major_version;
  *minor = r->api->pjrt_api_version.minor_version;
  return 0;
}

int rtp_process_index(int64_t id) {
  std::lock_guard<std::mutex> lk(g_mu);
  Resources* r = find_res(id);
  if (!r) return -1;
  PJRT_Client_ProcessIndex_Args a;
  std::memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Client_ProcessIndex_Args_STRUCT_SIZE;
  a.client = r->client;
  if (take_error(r->api, r->api->PJRT_Client_ProcessIndex(&a), nullptr))
    return -2;
  return a.process_index;
}

int rtp_device_count(int64_t id) {
  std::lock_guard<std::mutex> lk(g_mu);
  Resources* r = find_res(id);
  return r ? static_cast<int>(r->devices.size()) : -1;
}

int rtp_device_id(int64_t id, int idx) {
  std::lock_guard<std::mutex> lk(g_mu);
  Resources* r = find_res(id);
  if (!r || idx < 0 || idx >= static_cast<int>(r->devices.size()))
    return -1;
  PJRT_Device_GetDescription_Args gd;
  std::memset(&gd, 0, sizeof gd);
  gd.struct_size = PJRT_Device_GetDescription_Args_STRUCT_SIZE;
  gd.device = r->devices[static_cast<size_t>(idx)];
  if (take_error(r->api, r->api->PJRT_Device_GetDescription(&gd),
                 nullptr))
    return -2;
  PJRT_DeviceDescription_Id_Args di;
  std::memset(&di, 0, sizeof di);
  di.struct_size = PJRT_DeviceDescription_Id_Args_STRUCT_SIZE;
  di.device_description = gd.device_description;
  if (take_error(r->api, r->api->PJRT_DeviceDescription_Id(&di), nullptr))
    return -2;
  return di.id;
}

// mdarray: host → device. dtype is a PJRT_Buffer_Type value; data must
// be dense row-major. Returns buffer id > 0, or 0 with *err filled.
int64_t rtp_buffer_from_host(int64_t res_id, const void* data, int dtype,
                             const int64_t* dims, int ndim, int dev_idx,
                             char* err, int errlen) {
  const PJRT_Api* api = nullptr;
  PJRT_Client_BufferFromHostBuffer_Args a;
  std::memset(&a, 0, sizeof a);
  std::optional<InflightGuard> guard;
  {
    std::lock_guard<std::mutex> lk(g_mu);
    Resources* r = find_res(res_id);
    if (!r || is_dying(res_id)) {
      set_err(err, errlen, "bad resources id");
      return 0;
    }
    if (dev_idx < 0 || dev_idx >= static_cast<int>(r->devices.size())) {
      set_err(err, errlen, "bad device index");
      return 0;
    }
    api = r->api;
    a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    a.client = r->client;
    a.data = data;
    a.type = static_cast<PJRT_Buffer_Type>(dtype);
    a.dims = dims;
    a.num_dims = static_cast<size_t>(ndim);
    a.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    a.device = r->devices[static_cast<size_t>(dev_idx)];
    guard.emplace(res_id);
  }
  // the staging copy AND the host-pointer await both run OUTSIDE the
  // registry lock (a multi-GB upload must not serialize unrelated
  // calls); the inflight guard is held through cleanup/registration so
  // rtp_resources_destroy cannot free the client/plugin under us
  std::string msg;
  if (take_error(api, api->PJRT_Client_BufferFromHostBuffer(&a), &msg)) {
    set_err(err, errlen, "BufferFromHostBuffer: " + msg);
    return 0;
  }
  bool bad = await_event(api, a.done_with_host_buffer, &msg);
  if (bad) {
    // a failed/aborted transfer must NOT hand back a live-looking
    // buffer full of undefined bytes
    set_err(err, errlen, "done_with_host_buffer: " + msg);
    PJRT_Buffer_Destroy_Args d;
    std::memset(&d, 0, sizeof d);
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = a.buffer;
    take_error(api, api->PJRT_Buffer_Destroy(&d), nullptr);
    return 0;
  }
  // register while the guard is still held: a concurrent destroy is
  // parked in its drain loop and will orphan-sweep this buffer after
  std::lock_guard<std::mutex> lk(g_mu);
  int64_t id = g_next_id++;
  g_buf[id] = Buffer{res_id, a.buffer};
  return id;
}

int rtp_buffer_ndim(int64_t id) {
  std::lock_guard<std::mutex> lk(g_mu);
  Buffer* b = find_buf(id);
  if (!b) return -1;
  Resources* r = find_res(b->res_id);
  if (!r) return -1;
  PJRT_Buffer_Dimensions_Args a;
  std::memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  a.buffer = b->buf;
  if (take_error(r->api, r->api->PJRT_Buffer_Dimensions(&a), nullptr))
    return -2;
  return static_cast<int>(a.num_dims);
}

int rtp_buffer_dims(int64_t id, int64_t* out, int cap) {
  std::lock_guard<std::mutex> lk(g_mu);
  Buffer* b = find_buf(id);
  if (!b) return -1;
  Resources* r = find_res(b->res_id);
  if (!r) return -1;
  PJRT_Buffer_Dimensions_Args a;
  std::memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  a.buffer = b->buf;
  if (take_error(r->api, r->api->PJRT_Buffer_Dimensions(&a), nullptr))
    return -2;
  int n = static_cast<int>(a.num_dims);
  for (int i = 0; i < n && i < cap; ++i) out[i] = a.dims[i];
  return n;
}

int rtp_buffer_dtype(int64_t id) {
  std::lock_guard<std::mutex> lk(g_mu);
  Buffer* b = find_buf(id);
  if (!b) return -1;
  Resources* r = find_res(b->res_id);
  if (!r) return -1;
  PJRT_Buffer_ElementType_Args a;
  std::memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
  a.buffer = b->buf;
  if (take_error(r->api, r->api->PJRT_Buffer_ElementType(&a), nullptr))
    return -2;
  return static_cast<int>(a.type);
}

// Non-blocking readiness poll (interruptible::synchronize's poll step).
int rtp_buffer_ready(int64_t id) {
  std::lock_guard<std::mutex> lk(g_mu);
  Buffer* b = find_buf(id);
  if (!b) return -1;
  Resources* r = find_res(b->res_id);
  if (!r) return -1;
  PJRT_Buffer_ReadyEvent_Args re;
  std::memset(&re, 0, sizeof re);
  re.struct_size = PJRT_Buffer_ReadyEvent_Args_STRUCT_SIZE;
  re.buffer = b->buf;
  if (take_error(r->api, r->api->PJRT_Buffer_ReadyEvent(&re), nullptr))
    return -2;
  PJRT_Event_IsReady_Args ir;
  std::memset(&ir, 0, sizeof ir);
  ir.struct_size = PJRT_Event_IsReady_Args_STRUCT_SIZE;
  ir.event = re.event;
  bool bad = take_error(r->api, r->api->PJRT_Event_IsReady(&ir), nullptr);
  PJRT_Event_Destroy_Args d;
  std::memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = re.event;
  take_error(r->api, r->api->PJRT_Event_Destroy(&d), nullptr);
  if (bad) return -2;
  return ir.is_ready ? 1 : 0;
}

// Blocking sync on the buffer (the stream_syncer role).
int rtp_buffer_sync(int64_t id) {
  PJRT_Event* ev = nullptr;
  const PJRT_Api* api = nullptr;
  std::optional<InflightGuard> guard;
  {
    std::lock_guard<std::mutex> lk(g_mu);
    Buffer* b = find_buf(id);
    if (!b) return -1;
    if (is_dying(b->res_id)) return -1;
    Resources* r = find_res(b->res_id);
    if (!r) return -1;
    api = r->api;
    PJRT_Buffer_ReadyEvent_Args re;
    std::memset(&re, 0, sizeof re);
    re.struct_size = PJRT_Buffer_ReadyEvent_Args_STRUCT_SIZE;
    re.buffer = b->buf;
    if (take_error(api, api->PJRT_Buffer_ReadyEvent(&re), nullptr))
      return -2;
    ev = re.event;
    guard.emplace(b->res_id);  // under the SAME lock as the liveness
                               // check
  }
  // await OUTSIDE the registry lock: a slow device must not block
  // unrelated resource/buffer calls; the inflight guard keeps
  // rtp_resources_destroy from freeing the client under us
  std::string msg;
  return await_event(api, ev, &msg) ? -2 : 0;
}

// Device → host copy (blocking). out must hold nbytes.
int rtp_buffer_to_host(int64_t id, void* out, int64_t nbytes, char* err,
                       int errlen) {
  PJRT_Event* ev = nullptr;
  const PJRT_Api* api = nullptr;
  std::optional<InflightGuard> guard;
  {
    std::lock_guard<std::mutex> lk(g_mu);
    Buffer* b = find_buf(id);
    if (!b || is_dying(b->res_id)) {
      set_err(err, errlen, "bad buffer id");
      return -1;
    }
    Resources* r = find_res(b->res_id);
    if (!r) {
      set_err(err, errlen, "bad buffer id");
      return -1;
    }
    api = r->api;
    PJRT_Buffer_ToHostBuffer_Args a;
    std::memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    a.src = b->buf;
    a.dst = out;
    a.dst_size = static_cast<size_t>(nbytes);
    std::string msg;
    if (take_error(api, api->PJRT_Buffer_ToHostBuffer(&a), &msg)) {
      set_err(err, errlen, "ToHostBuffer: " + msg);
      return -2;
    }
    ev = a.event;
    guard.emplace(b->res_id);
  }
  std::string msg;
  if (await_event(api, ev, &msg)) {
    set_err(err, errlen, "copy event: " + msg);
    return -2;
  }
  return 0;
}

// Required host bytes for a device buffer (ToHostBuffer size query).
int64_t rtp_buffer_host_nbytes(int64_t id) {
  std::lock_guard<std::mutex> lk(g_mu);
  Buffer* b = find_buf(id);
  if (!b) return -1;
  Resources* r = find_res(b->res_id);
  if (!r) return -1;
  PJRT_Buffer_ToHostBuffer_Args a;
  std::memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  a.src = b->buf;
  a.dst = nullptr;  // size query
  if (take_error(r->api, r->api->PJRT_Buffer_ToHostBuffer(&a), nullptr))
    return -2;
  return static_cast<int64_t>(a.dst_size);
}

void rtp_buffer_destroy(int64_t id) {
  std::lock_guard<std::mutex> lk(g_mu);
  auto it = g_buf.find(id);
  if (it == g_buf.end()) return;
  Resources* r = find_res(it->second.res_id);
  if (r) {
    PJRT_Buffer_Destroy_Args d;
    std::memset(&d, 0, sizeof d);
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = it->second.buf;
    take_error(r->api, r->api->PJRT_Buffer_Destroy(&d), nullptr);
  }
  g_buf.erase(it);
}

}  // extern "C"
