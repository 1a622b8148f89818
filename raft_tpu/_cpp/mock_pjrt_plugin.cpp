// Minimal in-tree PJRT plugin: a host-memory "device" behind the real
// GetPjrtApi entry point.
//
// Role: the test double for raft_tpu_pjrt.cpp — the C++ resources/
// mdarray layer is exercised against this plugin on any machine (the
// same way the comms tests run on the virtual CPU mesh, SURVEY.md §4),
// while production loads libtpu through the identical
// dlopen + C API path. Implements only the subset the layer calls:
// errors, events (always-ready), client create/destroy/platform/
// devices, host↔device buffer copies, dims/dtype queries.

#include <cstdint>
#include <cstring>
#include <new>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

// The C API types are opaque declarations; the plugin owns their
// definitions.
struct PJRT_Error {
  std::string msg;
  PJRT_Error_Code code = PJRT_Error_Code_INTERNAL;
};

struct PJRT_Event {};  // host memory is synchronous: always ready

struct PJRT_DeviceDescription {
  int id = 0;
};

struct PJRT_Device {
  PJRT_DeviceDescription desc;
};

struct PJRT_Client {
  std::vector<PJRT_Device> devices;
  std::vector<PJRT_Device*> device_ptrs;
};

struct PJRT_Buffer {
  std::vector<char> data;
  std::vector<int64_t> dims;
  PJRT_Buffer_Type type = PJRT_Buffer_Type_INVALID;
};

namespace {

size_t itemsize(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_PRED:
    case PJRT_Buffer_Type_S8:
    case PJRT_Buffer_Type_U8:
      return 1;
    case PJRT_Buffer_Type_S16:
    case PJRT_Buffer_Type_U16:
    case PJRT_Buffer_Type_F16:
    case PJRT_Buffer_Type_BF16:
      return 2;
    case PJRT_Buffer_Type_S32:
    case PJRT_Buffer_Type_U32:
    case PJRT_Buffer_Type_F32:
      return 4;
    case PJRT_Buffer_Type_S64:
    case PJRT_Buffer_Type_U64:
    case PJRT_Buffer_Type_F64:
      return 8;
    default:
      return 0;
  }
}

PJRT_Error* err(const std::string& m) {
  auto* e = new PJRT_Error;
  e->msg = m;
  return e;
}

// ---- errors ----
void ErrorDestroy(PJRT_Error_Destroy_Args* a) { delete a->error; }

void ErrorMessage(PJRT_Error_Message_Args* a) {
  a->message = a->error->msg.c_str();
  a->message_size = a->error->msg.size();
}

PJRT_Error* ErrorGetCode(PJRT_Error_GetCode_Args* a) {
  a->code = a->error->code;
  return nullptr;
}

// ---- events (always ready) ----
PJRT_Error* EventDestroy(PJRT_Event_Destroy_Args* a) {
  delete a->event;
  return nullptr;
}

PJRT_Error* EventIsReady(PJRT_Event_IsReady_Args* a) {
  a->is_ready = true;
  return nullptr;
}

PJRT_Error* EventError(PJRT_Event_Error_Args*) { return nullptr; }

PJRT_Error* EventAwait(PJRT_Event_Await_Args*) { return nullptr; }

// ---- plugin / client ----
PJRT_Error* PluginInitialize(PJRT_Plugin_Initialize_Args*) {
  return nullptr;
}

PJRT_Error* ClientCreate(PJRT_Client_Create_Args* a) {
  auto* c = new PJRT_Client;
  c->devices.resize(2);  // two fake devices exercise device indexing
  for (int i = 0; i < 2; ++i) c->devices[static_cast<size_t>(i)].desc.id = i;
  for (auto& d : c->devices) c->device_ptrs.push_back(&d);
  a->client = c;
  return nullptr;
}

PJRT_Error* ClientDestroy(PJRT_Client_Destroy_Args* a) {
  delete a->client;
  return nullptr;
}

PJRT_Error* ClientPlatformName(PJRT_Client_PlatformName_Args* a) {
  static const char kName[] = "mockcpu";
  a->platform_name = kName;
  a->platform_name_size = sizeof(kName) - 1;
  return nullptr;
}

PJRT_Error* ClientProcessIndex(PJRT_Client_ProcessIndex_Args* a) {
  a->process_index = 0;
  return nullptr;
}

PJRT_Error* ClientDevices(PJRT_Client_Devices_Args* a) {
  a->devices = a->client->device_ptrs.data();
  a->num_devices = a->client->device_ptrs.size();
  return nullptr;
}

PJRT_Error* ClientAddressableDevices(
    PJRT_Client_AddressableDevices_Args* a) {
  a->addressable_devices = a->client->device_ptrs.data();
  a->num_addressable_devices = a->client->device_ptrs.size();
  return nullptr;
}

PJRT_Error* DeviceGetDescription(PJRT_Device_GetDescription_Args* a) {
  a->device_description = &a->device->desc;
  return nullptr;
}

PJRT_Error* DeviceDescriptionId(PJRT_DeviceDescription_Id_Args* a) {
  a->id = a->device_description->id;
  return nullptr;
}

// ---- buffers ----
PJRT_Error* BufferFromHostBuffer(
    PJRT_Client_BufferFromHostBuffer_Args* a) {
  size_t isz = itemsize(a->type);
  if (isz == 0) return err("mock plugin: unsupported dtype");
  if (a->num_byte_strides != 0 && a->byte_strides != nullptr)
    return err("mock plugin: dense layouts only");
  size_t n = isz;
  for (size_t i = 0; i < a->num_dims; ++i)
    n *= static_cast<size_t>(a->dims[i]);
  auto* b = new PJRT_Buffer;
  b->data.assign(static_cast<const char*>(a->data),
                 static_cast<const char*>(a->data) + n);
  b->dims.assign(a->dims, a->dims + a->num_dims);
  b->type = a->type;
  a->buffer = b;
  a->done_with_host_buffer = new PJRT_Event;
  return nullptr;
}

PJRT_Error* BufferDestroy(PJRT_Buffer_Destroy_Args* a) {
  delete a->buffer;
  return nullptr;
}

PJRT_Error* BufferElementType(PJRT_Buffer_ElementType_Args* a) {
  a->type = a->buffer->type;
  return nullptr;
}

PJRT_Error* BufferDimensions(PJRT_Buffer_Dimensions_Args* a) {
  a->dims = a->buffer->dims.data();
  a->num_dims = a->buffer->dims.size();
  return nullptr;
}

PJRT_Error* BufferToHostBuffer(PJRT_Buffer_ToHostBuffer_Args* a) {
  if (a->dst == nullptr) {
    a->dst_size = a->src->data.size();
    return nullptr;
  }
  if (a->dst_size < a->src->data.size())
    return err("mock plugin: dst too small");
  std::memcpy(a->dst, a->src->data.data(), a->src->data.size());
  a->event = new PJRT_Event;
  return nullptr;
}

PJRT_Error* BufferReadyEvent(PJRT_Buffer_ReadyEvent_Args* a) {
  a->event = new PJRT_Event;
  return nullptr;
}

PJRT_Api make_api() {
  PJRT_Api api;
  std::memset(&api, 0, sizeof api);
  api.struct_size = PJRT_Api_STRUCT_SIZE;
  api.pjrt_api_version.struct_size = PJRT_Api_Version_STRUCT_SIZE;
  api.pjrt_api_version.major_version = PJRT_API_MAJOR;
  api.pjrt_api_version.minor_version = PJRT_API_MINOR;
  api.PJRT_Error_Destroy = ErrorDestroy;
  api.PJRT_Error_Message = ErrorMessage;
  api.PJRT_Error_GetCode = ErrorGetCode;
  api.PJRT_Plugin_Initialize = PluginInitialize;
  api.PJRT_Event_Destroy = EventDestroy;
  api.PJRT_Event_IsReady = EventIsReady;
  api.PJRT_Event_Error = EventError;
  api.PJRT_Event_Await = EventAwait;
  api.PJRT_Client_Create = ClientCreate;
  api.PJRT_Client_Destroy = ClientDestroy;
  api.PJRT_Client_PlatformName = ClientPlatformName;
  api.PJRT_Client_ProcessIndex = ClientProcessIndex;
  api.PJRT_Client_Devices = ClientDevices;
  api.PJRT_Client_AddressableDevices = ClientAddressableDevices;
  api.PJRT_Client_BufferFromHostBuffer = BufferFromHostBuffer;
  api.PJRT_Device_GetDescription = DeviceGetDescription;
  api.PJRT_DeviceDescription_Id = DeviceDescriptionId;
  api.PJRT_Buffer_Destroy = BufferDestroy;
  api.PJRT_Buffer_ElementType = BufferElementType;
  api.PJRT_Buffer_Dimensions = BufferDimensions;
  api.PJRT_Buffer_ToHostBuffer = BufferToHostBuffer;
  api.PJRT_Buffer_ReadyEvent = BufferReadyEvent;
  return api;
}

}  // namespace

extern "C" const PJRT_Api* GetPjrtApi() {
  static PJRT_Api api = make_api();
  return &api;
}
