// Native inference predictor over the PJRT C API.
//
// The analog of the reference's C++ inference entry
// (/root/reference/paddle/fluid/inference/api/analysis_predictor.h and
// the train/demo C++ programs): load a saved model artifact and run it
// WITHOUT Python in the process. The artifact is what
// InferenceEngine.save_compiled writes (module.mlir with parameters
// baked as constants + native_manifest.txt + compile_options.pb), and
// execution goes through any PJRT C-API plugin (libtpu.so on a TPU
// host) loaded with dlopen at runtime — this file compiles against the
// official pjrt_c_api.h only, links nothing.
//
// Exported C surface (ctypes-friendly, thread-compatible; errors are
// returned as -1/NULL with the message kept per-thread):
//   ptpu_last_error()
//   ptpu_plugin_probe(plugin, &major, &minor, &num_devices)
//   ptpu_predictor_load(plugin, model_dir)
//   ptpu_predictor_num_inputs/_num_outputs(pred)
//   ptpu_predictor_io_info(pred, is_input, i, name_cap, name, dtype_cap,
//                          dtype, &rank, dims /*cap 16*/)
//   ptpu_predictor_output_bytes(pred, i)
//   ptpu_predictor_run(pred, const void** inputs, void** outputs)
//   ptpu_predictor_destroy(pred)
#include <dlfcn.h>
#include <cstdint>
#include <cctype>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

thread_local std::string g_err;

struct IoSpec {
  std::string name;
  std::string dtype;        // numpy name: float32, bfloat16, int64, ...
  std::vector<int64_t> dims;
  PJRT_Buffer_Type type;
  size_t elem_size;
  size_t num_elems() const {
    size_t n = 1;
    for (int64_t d : dims) n *= static_cast<size_t>(d);
    return n;
  }
  size_t bytes() const { return num_elems() * elem_size; }
};

struct Predictor {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  PJRT_Device* device = nullptr;
  std::vector<IoSpec> inputs, outputs;
};

bool dtype_info(const std::string& d, PJRT_Buffer_Type* t, size_t* sz) {
  struct Row { const char* n; PJRT_Buffer_Type t; size_t s; };
  static const Row rows[] = {
      {"bool", PJRT_Buffer_Type_PRED, 1},
      {"int8", PJRT_Buffer_Type_S8, 1},
      {"int16", PJRT_Buffer_Type_S16, 2},
      {"int32", PJRT_Buffer_Type_S32, 4},
      {"int64", PJRT_Buffer_Type_S64, 8},
      {"uint8", PJRT_Buffer_Type_U8, 1},
      {"uint16", PJRT_Buffer_Type_U16, 2},
      {"uint32", PJRT_Buffer_Type_U32, 4},
      {"uint64", PJRT_Buffer_Type_U64, 8},
      {"float16", PJRT_Buffer_Type_F16, 2},
      {"bfloat16", PJRT_Buffer_Type_BF16, 2},
      {"float32", PJRT_Buffer_Type_F32, 4},
      {"float64", PJRT_Buffer_Type_F64, 8},
  };
  for (const Row& r : rows) {
    if (d == r.n) {
      *t = r.t;
      *sz = r.s;
      return true;
    }
  }
  return false;
}

// Consume a PJRT_Error: record its message into g_err, destroy it.
// Returns true iff there WAS an error.
bool take_error(const PJRT_Api* api, PJRT_Error* err,
                const char* where) {
  if (err == nullptr) return false;
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  g_err = std::string(where) + ": " +
          std::string(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  return true;
}

bool await_event(const PJRT_Api* api, PJRT_Event* ev, const char* where) {
  PJRT_Event_Await_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  args.event = ev;
  PJRT_Error* err = api->PJRT_Event_Await(&args);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = ev;
  api->PJRT_Event_Destroy(&dargs);
  return !take_error(api, err, where);
}

bool read_file(const std::string& path, std::string* out) {
  std::ifstream f(path, std::ios::binary);
  if (!f) {
    g_err = "cannot open " + path;
    return false;
  }
  std::ostringstream ss;
  ss << f.rdbuf();
  *out = ss.str();
  return true;
}

bool parse_manifest(const std::string& path, std::vector<IoSpec>* ins,
                    std::vector<IoSpec>* outs) {
  std::ifstream f(path);
  if (!f) {
    g_err = "cannot open " + path;
    return false;
  }
  std::string word;
  if (!(f >> word) || word != "format") {
    g_err = "bad manifest (no format line)";
    return false;
  }
  f >> word;
  if (word != "ptpu-native-v1") {
    g_err = "unsupported manifest format " + word;
    return false;
  }
  for (std::vector<IoSpec>* dst : {ins, outs}) {
    size_t n;
    if (!(f >> word >> n) ||
        (word != "inputs" && word != "outputs")) {
      g_err = "bad manifest section header";
      return false;
    }
    for (size_t i = 0; i < n; ++i) {
      IoSpec s;
      int rank;
      if (!(f >> s.name >> s.dtype >> rank) || rank < 0 || rank > 16) {
        g_err = "bad manifest io line";
        return false;
      }
      for (int r = 0; r < rank; ++r) {
        int64_t d;
        if (!(f >> d)) {
          g_err = "bad manifest dims";
          return false;
        }
        s.dims.push_back(d);
      }
      if (!dtype_info(s.dtype, &s.type, &s.elem_size)) {
        g_err = "unsupported dtype " + s.dtype;
        return false;
      }
      dst->push_back(std::move(s));
    }
  }
  return true;
}

// Client create options from PTPU_PJRT_CREATE_OPTIONS="k=v;k2=v2"
// (value parsed as int64 when it looks like an integer, else string):
// plugins that need NamedValue create options get exactly the ones
// their JAX registration would pass.
struct CreateOptions {
  std::vector<std::string> keys, svals;  // stable storage
  std::vector<int64_t> ivals;
  std::vector<bool> is_int;
  std::vector<PJRT_NamedValue> named;
  void build() {
    named.clear();
    for (size_t i = 0; i < keys.size(); ++i) {
      PJRT_NamedValue v;
      std::memset(&v, 0, sizeof(v));
      v.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      v.name = keys[i].c_str();
      v.name_size = keys[i].size();
      if (is_int[i]) {
        v.type = PJRT_NamedValue_kInt64;
        v.int64_value = ivals[i];
        v.value_size = 1;
      } else {
        v.type = PJRT_NamedValue_kString;
        v.string_value = svals[i].c_str();
        v.value_size = svals[i].size();
      }
      named.push_back(v);
    }
  }
};

void parse_create_options(CreateOptions* co) {
  const char* env = std::getenv("PTPU_PJRT_CREATE_OPTIONS");
  if (env == nullptr) return;
  std::string all(env);
  size_t pos = 0;
  while (pos < all.size()) {
    size_t semi = all.find(';', pos);
    if (semi == std::string::npos) semi = all.size();
    std::string kv = all.substr(pos, semi - pos);
    pos = semi + 1;
    size_t eq = kv.find('=');
    if (eq == std::string::npos || eq == 0) continue;
    std::string key = kv.substr(0, eq), val = kv.substr(eq + 1);
    bool numeric = !val.empty();
    for (size_t i = 0; i < val.size(); ++i) {
      if (!(std::isdigit((unsigned char)val[i]) ||
            (i == 0 && val[i] == '-'))) {
        numeric = false;
        break;
      }
    }
    co->keys.push_back(key);
    co->is_int.push_back(numeric);
    co->ivals.push_back(numeric ? std::strtoll(val.c_str(), nullptr, 10)
                                : 0);
    co->svals.push_back(val);
  }
  co->build();
}

const PJRT_Api* load_api(const std::string& plugin, void** dl_out) {
  void* dl = dlopen(plugin.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (dl == nullptr) {
    g_err = std::string("dlopen failed: ") + dlerror();
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
  if (get == nullptr) {
    g_err = plugin + " does not export GetPjrtApi";
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api* api = get();
  if (api == nullptr) {
    g_err = "GetPjrtApi returned NULL";
    dlclose(dl);
    return nullptr;
  }
  *dl_out = dl;
  return api;
}

}  // namespace

extern "C" {

void ptpu_predictor_destroy(void* p);

const char* ptpu_last_error() { return g_err.c_str(); }

// Diagnostic: load the plugin, report its API version and (if a client
// can be created) the addressable device count. Returns 0 on full
// success, 1 if the plugin loads but client creation fails (probe
// still fills major/minor; num_devices = -1), -1 on load failure.
int ptpu_plugin_probe(const char* plugin_path, int* major, int* minor,
                      int* num_devices) {
  void* dl = nullptr;
  const PJRT_Api* api = load_api(plugin_path, &dl);
  if (api == nullptr) return -1;
  if (major) *major = api->pjrt_api_version.major_version;
  if (minor) *minor = api->pjrt_api_version.minor_version;
  if (num_devices) *num_devices = -1;

  CreateOptions co;
  parse_create_options(&co);
  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = co.named.data();
  cargs.num_options = co.named.size();
  if (take_error(api, api->PJRT_Client_Create(&cargs),
                 "PJRT_Client_Create")) {
    dlclose(dl);
    return 1;
  }
  PJRT_Client_AddressableDevices_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = cargs.client;
  int rc = 0;
  if (take_error(api, api->PJRT_Client_AddressableDevices(&dargs),
                 "PJRT_Client_AddressableDevices")) {
    rc = 1;
  } else if (num_devices) {
    *num_devices = static_cast<int>(dargs.num_addressable_devices);
  }
  PJRT_Client_Destroy_Args xargs;
  std::memset(&xargs, 0, sizeof(xargs));
  xargs.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
  xargs.client = cargs.client;
  take_error(api, api->PJRT_Client_Destroy(&xargs),
             "PJRT_Client_Destroy");
  dlclose(dl);
  return rc;
}

void* ptpu_predictor_load(const char* plugin_path,
                          const char* model_dir) {
  auto pred = new Predictor();
  std::string dir(model_dir);
  if (!parse_manifest(dir + "/native_manifest.txt", &pred->inputs,
                      &pred->outputs)) {
    delete pred;
    return nullptr;
  }
  std::string module, copts;
  if (!read_file(dir + "/module.mlir", &module) ||
      !read_file(dir + "/compile_options.pb", &copts)) {
    delete pred;
    return nullptr;
  }
  pred->api = load_api(plugin_path, &pred->dl);
  if (pred->api == nullptr) {
    delete pred;
    return nullptr;
  }
  const PJRT_Api* api = pred->api;

  CreateOptions co;
  parse_create_options(&co);
  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = co.named.data();
  cargs.num_options = co.named.size();
  if (take_error(api, api->PJRT_Client_Create(&cargs),
                 "PJRT_Client_Create")) {
    delete pred;
    return nullptr;
  }
  pred->client = cargs.client;

  PJRT_Client_AddressableDevices_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = pred->client;
  if (take_error(api, api->PJRT_Client_AddressableDevices(&dargs),
                 "PJRT_Client_AddressableDevices") ||
      dargs.num_addressable_devices == 0) {
    if (g_err.empty()) g_err = "no addressable devices";
    ptpu_predictor_destroy(pred);
    return nullptr;
  }
  pred->device = dargs.addressable_devices[0];

  PJRT_Program program;
  std::memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = module.data();
  program.code_size = module.size();
  static const char kFormat[] = "mlir";
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args pargs;
  std::memset(&pargs, 0, sizeof(pargs));
  pargs.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  pargs.client = pred->client;
  pargs.program = &program;
  pargs.compile_options = copts.data();
  pargs.compile_options_size = copts.size();
  if (take_error(api, api->PJRT_Client_Compile(&pargs),
                 "PJRT_Client_Compile")) {
    ptpu_predictor_destroy(pred);
    return nullptr;
  }
  pred->exec = pargs.executable;
  return pred;
}

int ptpu_predictor_num_inputs(void* p) {
  return static_cast<int>(static_cast<Predictor*>(p)->inputs.size());
}

int ptpu_predictor_num_outputs(void* p) {
  return static_cast<int>(static_cast<Predictor*>(p)->outputs.size());
}

long ptpu_predictor_output_bytes(void* p, int i) {
  auto* pred = static_cast<Predictor*>(p);
  if (i < 0 || i >= static_cast<int>(pred->outputs.size())) return -1;
  return static_cast<long>(pred->outputs[i].bytes());
}

int ptpu_predictor_io_info(void* p, int is_input, int i, int name_cap,
                           char* name, int dtype_cap, char* dtype,
                           int* rank, int64_t* dims /* cap >= 16 */) {
  auto* pred = static_cast<Predictor*>(p);
  const auto& list = is_input ? pred->inputs : pred->outputs;
  if (i < 0 || i >= static_cast<int>(list.size())) {
    g_err = "io index out of range";
    return -1;
  }
  const IoSpec& s = list[i];
  std::snprintf(name, name_cap, "%s", s.name.c_str());
  std::snprintf(dtype, dtype_cap, "%s", s.dtype.c_str());
  *rank = static_cast<int>(s.dims.size());
  for (size_t r = 0; r < s.dims.size(); ++r) dims[r] = s.dims[r];
  return 0;
}

int ptpu_predictor_run(void* p, const void** input_data,
                       void** output_data) {
  auto* pred = static_cast<Predictor*>(p);
  const PJRT_Api* api = pred->api;
  std::vector<PJRT_Buffer*> in_bufs(pred->inputs.size(), nullptr);
  int rc = -1;
  std::vector<PJRT_Buffer*> out_bufs;

  for (size_t i = 0; i < pred->inputs.size(); ++i) {
    const IoSpec& s = pred->inputs[i];
    PJRT_Client_BufferFromHostBuffer_Args bargs;
    std::memset(&bargs, 0, sizeof(bargs));
    bargs.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    bargs.client = pred->client;
    bargs.data = input_data[i];
    bargs.type = s.type;
    bargs.dims = s.dims.data();
    bargs.num_dims = s.dims.size();
    bargs.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    bargs.device = pred->device;
    if (take_error(api, api->PJRT_Client_BufferFromHostBuffer(&bargs),
                   "PJRT_Client_BufferFromHostBuffer")) {
      goto cleanup;
    }
    in_bufs[i] = bargs.buffer;
    if (!await_event(api, bargs.done_with_host_buffer,
                     "host-buffer transfer")) {
      goto cleanup;
    }
  }

  {
    PJRT_ExecuteOptions opts;
    std::memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    out_bufs.assign(pred->outputs.size(), nullptr);
    PJRT_Buffer** out_list = out_bufs.data();
    PJRT_Buffer* const* arg_list = in_bufs.data();
    PJRT_Event* done = nullptr;

    PJRT_LoadedExecutable_Execute_Args eargs;
    std::memset(&eargs, 0, sizeof(eargs));
    eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    eargs.executable = pred->exec;
    eargs.options = &opts;
    eargs.argument_lists = &arg_list;
    eargs.num_devices = 1;
    eargs.num_args = in_bufs.size();
    eargs.output_lists = &out_list;
    eargs.device_complete_events = &done;
    eargs.execute_device = pred->device;
    if (take_error(api, api->PJRT_LoadedExecutable_Execute(&eargs),
                   "PJRT_LoadedExecutable_Execute")) {
      goto cleanup;
    }
    if (!await_event(api, done, "execute")) goto cleanup;
  }

  for (size_t i = 0; i < pred->outputs.size(); ++i) {
    PJRT_Buffer_ToHostBuffer_Args targs;
    std::memset(&targs, 0, sizeof(targs));
    targs.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    targs.src = out_bufs[i];
    targs.dst = nullptr;  // query required size first
    if (take_error(api, api->PJRT_Buffer_ToHostBuffer(&targs),
                   "PJRT_Buffer_ToHostBuffer(size)")) {
      goto cleanup;
    }
    if (targs.dst_size > pred->outputs[i].bytes()) {
      g_err = "output " + pred->outputs[i].name +
              " larger than manifest size";
      goto cleanup;
    }
    targs.dst = output_data[i];
    if (take_error(api, api->PJRT_Buffer_ToHostBuffer(&targs),
                   "PJRT_Buffer_ToHostBuffer")) {
      goto cleanup;
    }
    if (!await_event(api, targs.event, "device-to-host copy")) {
      goto cleanup;
    }
  }
  rc = 0;

cleanup:
  for (PJRT_Buffer* b : in_bufs) {
    if (b == nullptr) continue;
    PJRT_Buffer_Destroy_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    a.buffer = b;
    take_error(api, api->PJRT_Buffer_Destroy(&a), "buffer destroy");
  }
  for (PJRT_Buffer* b : out_bufs) {
    if (b == nullptr) continue;
    PJRT_Buffer_Destroy_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    a.buffer = b;
    take_error(api, api->PJRT_Buffer_Destroy(&a), "buffer destroy");
  }
  return rc;
}

void ptpu_predictor_destroy(void* p) {
  auto* pred = static_cast<Predictor*>(p);
  if (pred == nullptr) return;
  const PJRT_Api* api = pred->api;
  if (pred->exec != nullptr) {
    PJRT_LoadedExecutable_Destroy_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    a.executable = pred->exec;
    take_error(api, api->PJRT_LoadedExecutable_Destroy(&a),
               "executable destroy");
  }
  if (pred->client != nullptr) {
    PJRT_Client_Destroy_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    a.client = pred->client;
    take_error(api, api->PJRT_Client_Destroy(&a), "client destroy");
  }
  if (pred->dl != nullptr) dlclose(pred->dl);
  delete pred;
}

}  // extern "C"
