// Device-engine bridge: routes the C/JNI surface onto the TPU path.
//
// The reference's JNI surface drives the CUDA engine directly
// (RowConversionJni.cpp:24-45 → spark_rapids_jni::convert_to_rows).  The
// TPU analog chosen here (SURVEY §7: "C++ core ... or an embedded-runtime
// bridge") is an embedded-Python trampoline: when the process hosts a
// CPython runtime (a PySpark executor, a JVM that initialized one, or the
// test harness), libsrjt forwards a host table handle to
// spark_rapids_jni_tpu.bridge, which reads the table through this same
// library's C accessors, runs the JAX/TPU engine, and hands the packed
// JCUDF bytes back through srjt_rows_adopt — so bytes entering the JNI
// surface are transcoded by the device engine, with the host C++ engine as
// the fallback tier.
//
// No link-time libpython dependency: the CPython C API is resolved with
// dlsym(RTLD_DEFAULT) at first use, so the .so still loads into a plain
// JVM (srjt_device_available() then reports 0 and callers stay on the
// host engine).

#include <cstdint>
#include <cstdlib>
#include <dlfcn.h>
#include <mutex>

namespace {

// minimal CPython C API surface, resolved dynamically
using PyGILState_Ensure_t = int (*)();
using PyGILState_Release_t = void (*)(int);
using PyImport_ImportModule_t = void* (*)(const char*);
using PyObject_GetAttrString_t = void* (*)(void*, const char*);
using PyObject_CallFunction_t = void* (*)(void*, const char*, ...);
using PyLong_AsLongLong_t = long long (*)(void*);
using PyErr_Occurred_t = void* (*)();
using PyErr_Clear_t = void (*)();
using Py_DecRef_t = void (*)(void*);
using Py_IsInitialized_t = int (*)();

struct PyApi {
  PyGILState_Ensure_t gil_ensure = nullptr;
  PyGILState_Release_t gil_release = nullptr;
  PyImport_ImportModule_t import_module = nullptr;
  PyObject_GetAttrString_t getattr = nullptr;
  PyObject_CallFunction_t call = nullptr;
  PyLong_AsLongLong_t as_longlong = nullptr;
  PyErr_Occurred_t err_occurred = nullptr;
  PyErr_Clear_t err_clear = nullptr;
  Py_DecRef_t decref = nullptr;
  Py_IsInitialized_t is_initialized = nullptr;
  bool ok = false;
};

const PyApi& py_api() {
  static PyApi api;
  static std::once_flag once;
  std::call_once(once, [] {
    void* self = RTLD_DEFAULT;
    api.gil_ensure = reinterpret_cast<PyGILState_Ensure_t>(
        dlsym(self, "PyGILState_Ensure"));
    api.gil_release = reinterpret_cast<PyGILState_Release_t>(
        dlsym(self, "PyGILState_Release"));
    api.import_module = reinterpret_cast<PyImport_ImportModule_t>(
        dlsym(self, "PyImport_ImportModule"));
    api.getattr = reinterpret_cast<PyObject_GetAttrString_t>(
        dlsym(self, "PyObject_GetAttrString"));
    api.call = reinterpret_cast<PyObject_CallFunction_t>(
        dlsym(self, "PyObject_CallFunction"));
    api.as_longlong = reinterpret_cast<PyLong_AsLongLong_t>(
        dlsym(self, "PyLong_AsLongLong"));
    api.err_occurred = reinterpret_cast<PyErr_Occurred_t>(
        dlsym(self, "PyErr_Occurred"));
    api.err_clear = reinterpret_cast<PyErr_Clear_t>(dlsym(self, "PyErr_Clear"));
    api.decref = reinterpret_cast<Py_DecRef_t>(dlsym(self, "Py_DecRef"));
    api.is_initialized = reinterpret_cast<Py_IsInitialized_t>(
        dlsym(self, "Py_IsInitialized"));
    api.ok = api.gil_ensure && api.gil_release && api.import_module
             && api.getattr && api.call && api.as_longlong
             && api.err_occurred && api.err_clear && api.decref
             && api.is_initialized;
  });
  return api;
}

// Runtime kill switch (same convention as the Pallas dispatch's
// SRJT_PALLAS toggle): SRJT_DEVICE=0 forces the host C++ engine even when
// an embedded runtime is reachable — the operator escape hatch for
// non-TPU executors where the "device" path is just slower.
bool device_disabled() {
  const char* v = std::getenv("SRJT_DEVICE");
  return v && v[0] == '0' && v[1] == '\0';
}

// call spark_rapids_jni_tpu.bridge.<fn>(handle) → int64 result handle
void* call_bridge(const char* fn, void* handle, const int32_t* type_ids,
                  const int32_t* scales, int32_t ncols) {
  if (device_disabled()) return nullptr;
  const PyApi& py = py_api();
  if (!py.ok || !py.is_initialized()) return nullptr;
  int gil = py.gil_ensure();
  void* result_handle = nullptr;
  void* mod = py.import_module("spark_rapids_jni_tpu.bridge");
  if (mod) {
    void* f = py.getattr(mod, fn);
    if (f) {
      void* res = type_ids
          ? py.call(f, "LLLl", static_cast<long long>(
                        reinterpret_cast<intptr_t>(handle)),
                    static_cast<long long>(
                        reinterpret_cast<intptr_t>(type_ids)),
                    static_cast<long long>(
                        reinterpret_cast<intptr_t>(scales)),
                    static_cast<long>(ncols))
          : py.call(f, "L", static_cast<long long>(
                        reinterpret_cast<intptr_t>(handle)));
      if (res) {
        long long v = py.as_longlong(res);
        if (!py.err_occurred()) {
          result_handle = reinterpret_cast<void*>(static_cast<intptr_t>(v));
        }
        py.decref(res);
      }
      py.decref(f);
    }
    py.decref(mod);
  }
  if (py.err_occurred()) py.err_clear();
  py.gil_release(gil);
  return result_handle;
}

}  // namespace

extern "C" {

// 1 when an initialized CPython runtime (and thus the JAX device engine)
// is reachable from this process.
int32_t srjt_device_available() {
  if (device_disabled()) return 0;
  const PyApi& py = py_api();
  return (py.ok && py.is_initialized()) ? 1 : 0;
}

// Host table handle → JCUDF RowBatches handle, transcoded by the DEVICE
// engine (JAX/TPU).  Returns nullptr when no runtime is available or the
// engine failed — callers fall back to srjt_to_rows (host engine).
void* srjt_to_rows_device(void* table_handle) {
  return call_bridge("to_rows_from_handle", table_handle, nullptr, nullptr, 0);
}

// JCUDF RowBatches handle (+ schema arrays) → host table handle via the
// device engine.  nullptr on failure — callers fall back to srjt_from_rows.
void* srjt_from_rows_device(void* rows_handle, const int32_t* type_ids,
                            const int32_t* scales, int32_t ncols) {
  return call_bridge("from_rows_from_handle", rows_handle, type_ids, scales,
                     ncols);
}

}  // extern "C"
