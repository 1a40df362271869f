// PJRT C-API bridge (SURVEY.md §7 P6: the native seam a Go/C++ eval worker
// calls instead of embedding Python).
//
// Flat C ABI over a dlopen'd PJRT plugin (e.g. libtpu.so): create a
// client, compile an MLIR (StableHLO) program, upload host buffers,
// execute, fetch outputs.  The scheduler's placement kernels
// are exported from JAX as StableHLO; this library runs them on the TPU
// with no Python in the loop — the Score(snapshot, evals) -> plans hot
// path of a production deployment.
//
// Build: see native/Makefile (g++ -shared, header-only dependency on the
// PJRT C API header; no protobuf/absl/XLA libs linked).

#include <dlfcn.h>
#include <string.h>

#include <cstdint>
#include <cstdio>
#include <string>
#include <vector>

#include "tensorflow/compiler/xla/pjrt/c/pjrt_c_api.h"

namespace {

void set_err(char* err, size_t errlen, const std::string& msg) {
  if (err && errlen) {
    snprintf(err, errlen, "%s", msg.c_str());
  }
}

std::string error_message(const PJRT_Api* api, PJRT_Error* e) {
  PJRT_Error_Message_Args margs;
  memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = e;
  api->PJRT_Error_Message(&margs);
  std::string out(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = e;
  api->PJRT_Error_Destroy(&dargs);
  return out;
}

// returns true on error (message copied to err)
bool check(const PJRT_Api* api, PJRT_Error* e, char* err, size_t errlen) {
  if (e == nullptr) return false;
  set_err(err, errlen, error_message(api, e));
  return true;
}

bool await_event(const PJRT_Api* api, PJRT_Event* ev, char* err,
                 size_t errlen) {
  if (ev == nullptr) return false;
  PJRT_Event_Await_Args aargs;
  memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = ev;
  PJRT_Error* e = api->PJRT_Event_Await(&aargs);
  bool bad = check(api, e, err, errlen);
  PJRT_Event_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = ev;
  api->PJRT_Event_Destroy(&dargs);
  return bad;
}

}  // namespace

extern "C" {

struct NtbClient {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;   // first addressable device
  size_t num_devices = 0;
};

// Client creation with plugin options (PJRT_NamedValue list).  Parallel
// arrays: names[i]; types[i] 0=string 1=int64; str_vals[i] (or null);
// int_vals[i].  Some plugins require create-options (topology, session
// id, compile mode) that their in-process JAX wrapper normally supplies.
NtbClient* ntb_create_with_options(const char* plugin_path, int n_opts,
                                   const char* const* names,
                                   const int* types,
                                   const char* const* str_vals,
                                   const int64_t* int_vals, char* err,
                                   size_t errlen) {
  void* dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!dl) {
    set_err(err, errlen, std::string("dlopen: ") + dlerror());
    return nullptr;
  }
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetPjrtApiFn>(dlsym(dl, "GetPjrtApi"));
  if (!get_api) {
    set_err(err, errlen, "plugin has no GetPjrtApi symbol");
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  if (!api) {
    set_err(err, errlen, "GetPjrtApi returned null");
    dlclose(dl);
    return nullptr;
  }

  {
    PJRT_Plugin_Initialize_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    if (check(api, api->PJRT_Plugin_Initialize(&args), err, errlen)) {
      dlclose(dl);
      return nullptr;
    }
  }

  std::vector<PJRT_NamedValue> opts(n_opts);
  for (int i = 0; i < n_opts; i++) {
    PJRT_NamedValue& nv = opts[i];
    memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = names[i];
    nv.name_size = strlen(names[i]);
    if (types[i] == 0) {
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = str_vals[i];
      nv.value_size = strlen(str_vals[i]);
    } else {
      nv.type = PJRT_NamedValue_kInt64;
      nv.int64_value = int_vals[i];
      nv.value_size = 1;
    }
  }

  PJRT_Client_Create_Args cargs;
  memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = opts.data();
  cargs.num_options = static_cast<size_t>(n_opts);
  if (check(api, api->PJRT_Client_Create(&cargs), err, errlen)) {
    dlclose(dl);
    return nullptr;
  }

  // NOTE on failure paths below: destroy the client but do NOT dlclose —
  // the plugin may have spawned background threads that would then
  // execute unmapped code (same rationale as ntb_destroy).
  auto destroy_client = [&]() {
    PJRT_Client_Destroy_Args xargs;
    memset(&xargs, 0, sizeof(xargs));
    xargs.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    xargs.client = cargs.client;
    api->PJRT_Client_Destroy(&xargs);
  };

  PJRT_Client_AddressableDevices_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = cargs.client;
  if (check(api, api->PJRT_Client_AddressableDevices(&dargs), err, errlen)) {
    destroy_client();
    return nullptr;
  }
  if (dargs.num_addressable_devices == 0) {
    set_err(err, errlen, "no addressable devices");
    destroy_client();
    return nullptr;
  }

  auto* c = new NtbClient();
  c->dl = dl;
  c->api = api;
  c->client = cargs.client;
  c->device = dargs.addressable_devices[0];
  c->num_devices = dargs.num_addressable_devices;
  return c;
}

NtbClient* ntb_create(const char* plugin_path, char* err, size_t errlen) {
  return ntb_create_with_options(plugin_path, 0, nullptr, nullptr, nullptr,
                                 nullptr, err, errlen);
}

void ntb_destroy(NtbClient* c) {
  if (!c) return;
  if (c->client) {
    PJRT_Client_Destroy_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    args.client = c->client;
    c->api->PJRT_Client_Destroy(&args);
  }
  // the plugin may have live background threads; leave it mapped
  delete c;
}

int ntb_device_count(NtbClient* c) {
  return c ? static_cast<int>(c->num_devices) : 0;
}

int ntb_platform(NtbClient* c, char* out, size_t outlen) {
  if (!out || outlen == 0) return -1;
  PJRT_Client_PlatformName_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  args.client = c->client;
  if (check(c->api, c->api->PJRT_Client_PlatformName(&args), out, outlen)) {
    return -1;
  }
  size_t n = args.platform_name_size < outlen - 1 ? args.platform_name_size
                                                  : outlen - 1;
  memcpy(out, args.platform_name, n);
  out[n] = 0;
  return 0;
}

// Compile an MLIR (StableHLO) program.  `options`/`options_size`: a
// serialized xla.CompileOptionsProto (the Python wrapper provides it).
void* ntb_compile(NtbClient* c, const char* code, size_t code_size,
                  const char* options, size_t options_size, char* err,
                  size_t errlen) {
  PJRT_Program program;
  memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(code);
  program.code_size = code_size;
  static const char kFormat[] = "mlir";
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  args.client = c->client;
  args.program = &program;
  args.compile_options = options;
  args.compile_options_size = options_size;
  if (check(c->api, c->api->PJRT_Client_Compile(&args), err, errlen)) {
    return nullptr;
  }
  return args.executable;
}

void ntb_executable_destroy(NtbClient* c, void* exec) {
  if (!c || !exec) return;
  PJRT_LoadedExecutable_Destroy_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  args.executable = static_cast<PJRT_LoadedExecutable*>(exec);
  c->api->PJRT_LoadedExecutable_Destroy(&args);
}

long ntb_num_outputs(NtbClient* c, void* exec, char* err, size_t errlen) {
  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = static_cast<PJRT_LoadedExecutable*>(exec);
  if (check(c->api, c->api->PJRT_LoadedExecutable_GetExecutable(&gargs), err,
            errlen)) {
    return -1;
  }
  long out = -1;
  PJRT_Executable_NumOutputs_Args nargs;
  memset(&nargs, 0, sizeof(nargs));
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.executable = gargs.executable;
  if (!check(c->api, c->api->PJRT_Executable_NumOutputs(&nargs), err,
             errlen)) {
    out = static_cast<long>(nargs.num_outputs);
  }
  // the caller owns the PJRT_Executable from GetExecutable
  PJRT_Executable_Destroy_Args xargs;
  memset(&xargs, 0, sizeof(xargs));
  xargs.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
  xargs.executable = gargs.executable;
  c->api->PJRT_Executable_Destroy(&xargs);
  return out;
}

// One synchronous execution on device 0.
//   inputs: n_in buffers; dtypes[i] is a PJRT_Buffer_Type; dims_flat holds
//   each input's dims back to back (ndims[i] each); data[i] host pointers.
//   outputs: n_out preallocated host buffers out_data[i] of capacity
//   out_cap[i] bytes; expected dims in out_dims_flat/out_ndims and element
//   byte width in out_elem — used to request a DENSE row-major host layout
//   (a TPU buffer's native layout is tiled; copying it raw would hand the
//   caller scrambled bytes).  Actual byte sizes land in out_sizes[i].
// Returns 0 on success, -1 on error (message in err).
int ntb_execute(NtbClient* c, void* exec, int n_in, const int* dtypes,
                const int64_t* dims_flat, const int* ndims,
                const void* const* data, int n_out, void* const* out_data,
                const int64_t* out_cap, const int64_t* out_dims_flat,
                const int* out_ndims, const int* out_elem,
                int64_t* out_sizes, char* err, size_t errlen) {
  const PJRT_Api* api = c->api;
  // n_out MUST match the program's output count: Execute fills the output
  // list to the executable's real arity, so a short vector would be
  // overrun (heap corruption, not an error return)
  {
    long real = ntb_num_outputs(c, exec, err, errlen);
    if (real < 0) return -1;
    if (real != n_out) {
      set_err(err, errlen, "executable has " + std::to_string(real) +
                               " outputs, caller provided " +
                               std::to_string(n_out));
      return -1;
    }
  }
  std::vector<PJRT_Buffer*> in_bufs;
  in_bufs.reserve(n_in);
  int rc = -1;
  std::vector<PJRT_Buffer*> out_bufs(n_out, nullptr);

  // ---- upload inputs ----
  size_t dim_off = 0;
  for (int i = 0; i < n_in; i++) {
    PJRT_Client_BufferFromHostBuffer_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    args.client = c->client;
    args.data = data[i];
    args.type = static_cast<PJRT_Buffer_Type>(dtypes[i]);
    args.dims = dims_flat + dim_off;
    args.num_dims = static_cast<size_t>(ndims[i]);
    dim_off += ndims[i];
    args.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    args.device = c->device;
    if (check(api, api->PJRT_Client_BufferFromHostBuffer(&args), err,
              errlen)) {
      goto cleanup;
    }
    in_bufs.push_back(args.buffer);
    if (await_event(api, args.done_with_host_buffer, err, errlen)) {
      goto cleanup;
    }
  }

  // ---- execute ----
  {
    PJRT_ExecuteOptions opts;
    memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    PJRT_Buffer* const* arg_list = in_bufs.data();
    PJRT_Buffer** out_list = out_bufs.data();
    PJRT_Event* dev_event = nullptr;

    PJRT_LoadedExecutable_Execute_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    args.executable = static_cast<PJRT_LoadedExecutable*>(exec);
    args.options = &opts;
    args.argument_lists = &arg_list;
    args.num_devices = 1;
    args.num_args = static_cast<size_t>(n_in);
    args.output_lists = &out_list;
    args.device_complete_events = &dev_event;
    if (check(api, api->PJRT_LoadedExecutable_Execute(&args), err, errlen)) {
      goto cleanup;
    }
    if (await_event(api, dev_event, err, errlen)) {
      goto cleanup;
    }
  }

  // ---- fetch outputs (dense row-major host layout) ----
  (void)out_dims_flat;   // kept in the ABI for stride-based plugins
  (void)out_elem;
  {
    for (int i = 0; i < n_out; i++) {
      int nd = out_ndims[i];
      // dense row-major: minor_to_major = [nd-1, ..., 0], no tiles
      // (the plugin only accepts Tiled descriptors, matching jaxlib's
      // ToLiteral path)
      std::vector<int64_t> m2m(nd);
      for (int d = 0; d < nd; d++) m2m[d] = nd - 1 - d;

      PJRT_Buffer_MemoryLayout layout;
      memset(&layout, 0, sizeof(layout));
      layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
      layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
      layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
      layout.tiled.minor_to_major = m2m.data();
      layout.tiled.minor_to_major_size = static_cast<size_t>(nd);

      PJRT_Buffer_ToHostBuffer_Args args;
      memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      args.src = out_bufs[i];
      args.host_layout = &layout;
      // size query
      if (check(api, api->PJRT_Buffer_ToHostBuffer(&args), err, errlen)) {
        goto cleanup;
      }
      if (static_cast<int64_t>(args.dst_size) > out_cap[i]) {
        set_err(err, errlen, "output " + std::to_string(i) + " needs " +
                                 std::to_string(args.dst_size) + " bytes, " +
                                 std::to_string(out_cap[i]) + " provided");
        goto cleanup;
      }
      out_sizes[i] = static_cast<int64_t>(args.dst_size);
      args.dst = out_data[i];
      if (check(api, api->PJRT_Buffer_ToHostBuffer(&args), err, errlen)) {
        goto cleanup;
      }
      if (await_event(api, args.event, err, errlen)) {
        goto cleanup;
      }
    }
  }
  rc = 0;

cleanup:
  for (PJRT_Buffer* b : in_bufs) {
    PJRT_Buffer_Destroy_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    args.buffer = b;
    api->PJRT_Buffer_Destroy(&args);
  }
  for (PJRT_Buffer* b : out_bufs) {
    if (!b) continue;
    PJRT_Buffer_Destroy_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    args.buffer = b;
    api->PJRT_Buffer_Destroy(&args);
  }
  return rc;
}

// ---------------------------------------------------------------------------
// Persistent device buffers (round-5 verdict #4).
//
// ntb_execute above re-uploads every argument per call — measured 4×
// slower than the JAX-driven path at bench scale, because the node
// tensors (attrs/cap/used: tens of MB) went host->device every wave.
// The production worker instead holds its cluster state DEVICE-RESIDENT:
//   ntb_upload           host array -> retained PJRT_Buffer handle
//   ntb_execute_resident run with handles; outputs RETAINED as handles
//                        (nothing crosses to the host)
//   ntb_fetch            one buffer -> host, dense row-major
//   ntb_buffer_free      drop a handle
// A wave then uploads only its per-eval deltas (constraint rows, round
// schedule — KBs), executes, fetches the compact result buffer, and can
// chain an output handle (the proposed-usage tensor) straight into the
// next wave's inputs without the host ever seeing it.

void* ntb_upload(NtbClient* c, int dtype, const int64_t* dims, int ndims,
                 const void* data, char* err, size_t errlen) {
  PJRT_Client_BufferFromHostBuffer_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  args.client = c->client;
  args.data = data;
  args.type = static_cast<PJRT_Buffer_Type>(dtype);
  args.dims = dims;
  args.num_dims = static_cast<size_t>(ndims);
  args.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  args.device = c->device;
  if (check(c->api, c->api->PJRT_Client_BufferFromHostBuffer(&args), err,
            errlen)) {
    return nullptr;
  }
  if (await_event(c->api, args.done_with_host_buffer, err, errlen)) {
    PJRT_Buffer_Destroy_Args dargs;
    memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    dargs.buffer = args.buffer;
    c->api->PJRT_Buffer_Destroy(&dargs);
    return nullptr;
  }
  return args.buffer;
}

void ntb_buffer_free(NtbClient* c, void* buf) {
  if (!c || !buf) return;
  PJRT_Buffer_Destroy_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  c->api->PJRT_Buffer_Destroy(&args);
}

// Execute with pre-uploaded buffer handles; outputs come back as RETAINED
// handles in out_bufs (caller frees with ntb_buffer_free or feeds them to
// a later execute).  Waits for device completion.
int ntb_execute_resident(NtbClient* c, void* exec, int n_in,
                         void* const* in_bufs, int n_out, void** out_bufs,
                         char* err, size_t errlen) {
  const PJRT_Api* api = c->api;
  long real = ntb_num_outputs(c, exec, err, errlen);
  if (real < 0) return -1;
  if (real != n_out) {
    set_err(err, errlen, "executable has " + std::to_string(real) +
                             " outputs, caller provided " +
                             std::to_string(n_out));
    return -1;
  }
  std::vector<PJRT_Buffer*> ins(n_in);
  for (int i = 0; i < n_in; i++)
    ins[i] = static_cast<PJRT_Buffer*>(in_bufs[i]);
  std::vector<PJRT_Buffer*> outs(n_out, nullptr);

  PJRT_ExecuteOptions opts;
  memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_Buffer* const* arg_list = ins.data();
  PJRT_Buffer** out_list = outs.data();
  PJRT_Event* dev_event = nullptr;

  PJRT_LoadedExecutable_Execute_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  args.executable = static_cast<PJRT_LoadedExecutable*>(exec);
  args.options = &opts;
  args.argument_lists = &arg_list;
  args.num_devices = 1;
  args.num_args = static_cast<size_t>(n_in);
  args.output_lists = &out_list;
  args.device_complete_events = &dev_event;
  if (check(api, api->PJRT_LoadedExecutable_Execute(&args), err, errlen)) {
    return -1;
  }
  if (await_event(api, dev_event, err, errlen)) {
    for (PJRT_Buffer* b : outs) {
      if (b) ntb_buffer_free(c, b);
    }
    return -1;
  }
  for (int i = 0; i < n_out; i++) out_bufs[i] = outs[i];
  return 0;
}

// Fetch one device buffer to host in dense row-major layout.  Returns the
// byte size, or -1 on error (including dst too small).
int64_t ntb_fetch(NtbClient* c, void* buf, void* dst, int64_t cap, char* err,
                  size_t errlen) {
  const PJRT_Api* api = c->api;
  PJRT_Buffer_Dimensions_Args dims_args;
  memset(&dims_args, 0, sizeof(dims_args));
  dims_args.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  dims_args.buffer = static_cast<PJRT_Buffer*>(buf);
  if (check(api, api->PJRT_Buffer_Dimensions(&dims_args), err, errlen)) {
    return -1;
  }
  int nd = static_cast<int>(dims_args.num_dims);
  std::vector<int64_t> m2m(nd);
  for (int d = 0; d < nd; d++) m2m[d] = nd - 1 - d;

  PJRT_Buffer_MemoryLayout layout;
  memset(&layout, 0, sizeof(layout));
  layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  layout.tiled.minor_to_major = m2m.data();
  layout.tiled.minor_to_major_size = static_cast<size_t>(nd);

  PJRT_Buffer_ToHostBuffer_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = static_cast<PJRT_Buffer*>(buf);
  args.host_layout = &layout;
  if (check(api, api->PJRT_Buffer_ToHostBuffer(&args), err, errlen)) {
    return -1;
  }
  if (static_cast<int64_t>(args.dst_size) > cap) {
    set_err(err, errlen,
            "buffer needs " + std::to_string(args.dst_size) + " bytes, " +
                std::to_string(cap) + " provided");
    return -1;
  }
  int64_t size = static_cast<int64_t>(args.dst_size);
  args.dst = dst;
  if (check(api, api->PJRT_Buffer_ToHostBuffer(&args), err, errlen)) {
    return -1;
  }
  if (await_event(api, args.event, err, errlen)) {
    return -1;
  }
  return size;
}

}  // extern "C"
